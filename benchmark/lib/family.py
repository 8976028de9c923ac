"""A model family is a directory of files, found by the ``"model"`` key of a
configuration's file the way a metric's reader is found by its name; there is
no registry to edit. ``benchmark/families/<model>/`` holds one file a role:

``adapter.py``    the program's model, step pieces and engine (the only role
                  that imports the program)
``weights.py``    the leaves: shapes, names, which are gains, compared parts
``reference.py``  the plain reference and its lower-precision controls
``needs.py``      FLOPs and bytes the algorithm needs, from shapes alone

``benchmark/README.md`` lists the functions of each role. A role is imported
when it is first asked for, so a reader that wants ``needs`` never imports
the program.
"""

from __future__ import annotations

import importlib
import os
import re
import sys
import types
from typing import Dict, List

ROLES = ("adapter", "weights", "reference", "needs")


def families_dir(root: str) -> str:
    return os.path.join(root, "benchmark", "families")


def families_found(root: str) -> List[str]:
    """Names of the directories that hold every role's file."""
    base = families_dir(root)
    if not os.path.isdir(base):
        return []
    return sorted(
        d for d in os.listdir(base)
        if all(os.path.isfile(os.path.join(base, d, role + ".py"))
               for role in ROLES))


class Family:
    """The four roles of one family, each a module imported on first use."""

    def __init__(self, name: str, path: str):
        # the real path: one family reached through a link is one package
        self.name, self.path = name, os.path.realpath(path)
        self._package = "benchmark_family_" + re.sub(r"\W", "_", name)

    def _ensure_package(self) -> None:
        # a package made of the directory, so that a family's files can
        # import one another (``from . import weights``)
        pkg = sys.modules.get(self._package)
        if pkg is None:
            pkg = types.ModuleType(self._package)
            pkg.__path__ = [self.path]
            sys.modules[self._package] = pkg
        elif list(pkg.__path__) != [self.path]:
            raise RuntimeError(
                f"family {self.name!r} is already loaded from "
                f"{list(pkg.__path__)}, not {self.path}")

    def __getattr__(self, role: str):
        if role not in ROLES:
            raise AttributeError(role)
        self._ensure_package()
        mod = importlib.import_module(f"{self._package}.{role}")
        setattr(self, role, mod)
        return mod

    def __repr__(self) -> str:
        return f"Family({self.name!r})"


def load_family(root: str, cfg: Dict) -> Family:
    """The family that ``cfg["model"]`` names. A missing or unknown name is
    an error that says which families there are; nothing is the default."""
    found = families_found(root)
    name = cfg.get("model")
    if name not in found:
        raise SystemExit(
            f"configuration {cfg.get('name')!r}: \"model\" is {name!r}; the "
            f"families under {families_dir(root)} are {found}")
    return Family(name, os.path.join(families_dir(root), name))
