"""The documents name files that exist. Every back-ticked path that begins
with one of the tree's top directories must be there, so that a PR which
deletes or moves a file finds the documents that still describe it.

Bare file names are not checked: the documents use them as shorthand
(``engine.py``) and for run-time artifacts (``journal.jsonl``). PERF.md,
ROADMAP.md and CHANGES.md are left out because they narrate history."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (
    "README.md", "OBSERVABILITY.md", "RESILIENCE.md", "SCALING.md",
    "COVERAGE.md", "paddle_tpu/analysis/RULES.md", "benchmark/README.md",
    "benchmark/families/deepseek_v2/README.md",
    ".claude/skills/verify/SKILL.md",
)
ROOTS = ("paddle_tpu/", "tests/", "tools/", "benchmark/", "examples/")
# a glob, a brace set or a <placeholder> is not one path
NOT_ONE_PATH = re.compile(r"[*?{}<>\[\]]")


def named_paths(text):
    """The paths a document names: the first word of each back-ticked span
    that begins with a top directory, less a ``::test`` or ``:line`` suffix
    and trailing punctuation."""
    out = set()
    # a span may wrap over a line's end; a fence's own marks are not spans
    for span in re.findall(r"`([^`]+)`", text.replace("```", "")):
        words = span.split()
        if not words or not words[0].startswith(ROOTS):
            continue
        word = words[0].split(":", 1)[0].rstrip(".,;)")
        if not NOT_ONE_PATH.search(word):
            out.add(word)
    return sorted(out)


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        paths = named_paths(f.read())
    assert paths, f"{doc} names no path: the rule reads nothing"
    missing = [p for p in paths if not os.path.exists(os.path.join(REPO, p))]
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
