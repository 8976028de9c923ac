"""The device-side names of the serving programs: for every instruction a
program executes, the seam scope and the family scope it was traced under.

The engine wraps each call across its model seam in a ``jax.named_scope``
(:data:`SEAMS`); a model family's own scopes (``gdn/*``, ``mla/*``,
``moe/*``) sit under them. Both reach the optimized program as each
instruction's ``metadata={op_name=...}``. A profiler trace names an executed
instruction by its text without that metadata, and a program by its module
name (``jit_serve_<kind>``), so :func:`table` gives, for each program the
engine ran, each executed instruction's text up to its metadata beside its
two scopes: a reader joins the trace's op events with it.

Cost: :func:`note` runs only where the engine's recompile sentinel sees a
new signature, after that dispatch. It keeps the program's trace
(``jitted.trace`` of the abstract arguments, which the dispatch has just
cached), not the jitted object, so nothing here holds an engine's weights or
pools. Nothing is noted under ``FLAGS_telemetry=off``. :func:`table` lowers
and compiles each noted program when first asked, which jax's cache answers
with the executable the dispatch compiled (a compile only where that is
gone): reading time, never a dispatch's.
"""

from __future__ import annotations

import re
from collections import deque
from typing import Dict, List, NamedTuple, Tuple

import jax

from . import trace

__all__ = ["SEAMS", "FAMILIES", "Program", "note", "abstract", "table",
           "scopes", "parse", "opcode", "reset"]

#: the engine's seam scopes (``serving/engine.py``'s program builders)
SEAMS = ("embed", "feed", "attn/project", "attn/cache_write", "attn/attend",
         "finish", "state", "state/write", "head", "sample", "counts")
#: first path segment of a model family's own scopes, nested under a seam
FAMILIES = ("gdn", "mla", "moe")
#: programs kept, the newest last (a serving cell runs a few dozen)
CAPACITY = 256

_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"(?:condition|body|to_apply|true_computation|"
                     r"false_computation)=%([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
#: the opcodes whose called computations execute as ops of their own
_CONTROL = ("while", "conditional", "call")


class Program(NamedTuple):
    kind: str       # decode, block_decode, prefill, extend or verify
    module: str     # the ``XLA Modules`` name: ``jit_serve_<kind>``
    ops: Dict[str, Tuple[str, str]]   # text up to metadata -> (seam, family)


_notes: "deque[Tuple[str, object]]" = deque(maxlen=CAPACITY)
_built: "deque[Program]" = deque(maxlen=CAPACITY)


def abstract(args):
    """``args`` as the ``ShapeDtypeStruct`` s a dispatch saw them: an
    uncommitted array without a sharding, as the dispatch lowered it, so
    that :func:`table` lowers to the very program the dispatch compiled and
    jax's own cache hands that executable back (a sharding given would add
    annotations: another module, a compile of its own)."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype,
        sharding=x.sharding if getattr(x, "committed", False) else None,
        weak_type=getattr(x, "weak_type", False)), args)


def note(kind: str, jitted, args) -> None:
    """Keep program ``kind``: ``jitted`` at the abstract ``args`` it was
    just dispatched with."""
    if trace.enabled():
        _notes.append((kind, jitted.trace(*args)))


def table() -> List[Program]:
    """Every noted program's executed instructions and their scopes."""
    while _notes:
        kind, traced = _notes.popleft()
        _built.append(parse(kind, traced.lower().compile().as_text()))
    return list(_built)


def reset() -> None:
    _notes.clear()
    _built.clear()


def scopes(op_name: str) -> Tuple[str, str]:
    """``jit(serve_decode)/finish/moe/experts/dot_general`` -> ``("finish",
    "moe/experts")``; ``("", "")`` outside every seam."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        two = "/".join(parts[i:i + 2])
        seam = two if two in SEAMS else part if part in SEAMS else ""
        if seam:
            rest = parts[i + 1 + seam.count("/"):]
            family = "/".join(rest[:2]) if rest and rest[0] in FAMILIES \
                else ""
            return seam, family
    return "", ""


def opcode(text: str) -> str:
    """The opcode of ``%name = <shape> <opcode>(...)``."""
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):            # a tuple shape: to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1]
    return rest.lstrip().split("(", 1)[0]


def parse(kind: str, text: str) -> Program:
    """The executed computations of compiled ``text`` (the entry and what
    its ``while``, ``conditional`` and ``call`` instructions run; a fusion's
    body is one op): each instruction's text up to its metadata and its
    scopes. An instruction the compiler made without metadata (a prefetch's
    copy, a layout change) takes the scopes of its first user that has
    some, else of its first operand."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    entry, cur = "", None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line == "}":
            cur = None
        elif cur is not None and " = " in line:
            body = line.strip()
            body = body[5:] if body.startswith("ROOT ") else body
            name = _OP_NAME.search(body)
            cur.append((body.split(", metadata={", 1)[0],
                        name.group(1) if name else "", opcode(body)))
    ops: Dict[str, Tuple[str, str]] = {}
    todo, seen = [(entry, ("", ""))], set()
    while todo:
        comp, caller = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        named = _inherit(comps[comp])
        for t, _, op in comps[comp]:
            # a loop the compiler made runs under its caller's scopes
            mine = named[t.split(" = ", 1)[0]] or caller
            ops[t] = mine
            if op in _CONTROL:
                todo += [(c, mine) for c in _CALLED.findall(t) + [
                    c.strip().lstrip("%") for b in _BRANCHES.findall(t)
                    for c in b.split(",")]]
    module = text.split(",", 1)[0].split()[-1]
    return Program(kind, module, ops)


def _inherit(instrs) -> Dict[str, Tuple[str, str]]:
    """Each instruction's scopes by its name; one without a seam scope of
    its own that the compiler made (no metadata, or an argument's name: a
    weight's relayout copy) takes its first named user's (a chain of
    copies: sweep until none moves), else its first named operand's."""
    named = {}
    for t, n, _ in instrs:
        got = scopes(n)
        named[t.split(" = ", 1)[0]] = got if got[0] or n.startswith(
            "jit(") else None
    edges = [(t.split(" = ", 1)[0],
              [u for u in re.findall(r"%[^\s,()]+", t.split(" = ", 1)[1])
               if u in named]) for t, _, _ in instrs]
    for to_operands in (True, False):
        moved = True
        while moved:
            moved = False
            for me, used in edges:
                if to_operands and named[me] and named[me][0]:
                    for u in used:
                        if named[u] is None:
                            named[u], moved = named[me], True
                elif not to_operands and named[me] is None:
                    got = [named[u] for u in used
                           if named[u] and named[u][0]]
                    if got:
                        named[me], moved = got[0], True
    return named
