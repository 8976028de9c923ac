"""Device time of the serving programs by the program's own names: the op
events of the traced stretch joined with the table the program keeps from
each executed instruction to its seam scope and its family scope
(``paddle_tpu.observability.device_names.table()``).

A program's kind is in its module name on the ``XLA Modules`` line: decode
(``jit_serve_decode``, ``jit_serve_block_decode``) or prefill
(``jit_serve_prefill``, ``jit_serve_extend``). An op event names its
instruction by its text without metadata and with its operands' shapes; the
table's text has the operands bare, so the two are joined by the
instruction's name and result shape, in the noted program of that
module name that holds most of the executed program's ops (the buckets of a
kind share a module name). Only leaf ops count: ``while``, ``conditional``
and ``call`` span their bodies, whose ops are events too.

This file imports the program (the table lives in the process that ran the
window). A program that keeps no table, or a run without a trace, has
nothing to read here: None.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import trace as TR

DECODE = ("jit_serve_decode", "jit_serve_block_decode")
PREFILL = ("jit_serve_prefill", "jit_serve_extend")
#: the seam scopes that a decode program's head and sampling rule run under
HEAD = ("head", "sample")
_CONTROL = ("while", "conditional", "call")


def key(text: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3 bf16[8]{0}``:
    what an op event's text and the table's share (not the opcode: the
    trace prints an async pair as ``async-start`` where the program's text
    has ``slice-start``). A name that is no instruction's text is its own
    key, and matches nothing."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text
    if rest.startswith("("):            # a tuple shape: to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return f"{name.strip().lstrip('%')} {rest[:i + 1]}"
    return f"{name.strip().lstrip('%')} {rest.partition(' ')[0]}"


def program_table():
    """The program's name table, keyed for the join: ``[(module, {key:
    (seam, family)})]``; None where the program keeps none."""
    try:
        from paddle_tpu.observability import device_names
    except ImportError:
        return None
    return [(p.module, {key(t): s for t, s in p.ops.items()})
            for p in device_names.table()]


def _add(acc: Dict[str, float], k: str, v: float) -> None:
    acc[k] = acc.get(k, 0.0) + v


def named_time(ctx, modules) -> Optional[Dict]:
    """Leaf-op device seconds of the programs whose module name is one of
    ``modules``, inside the traced stretch, by seam scope (``by_scope``),
    by seam and family scope (``by_family``) and, in each seam, by op family
    (``by_op``); ``unnamed`` by op family, ``unmatched`` the ops the join
    did not find (``unmatched_ops`` by op family); ``n`` programs, ``leaf``
    and ``program`` their seconds."""
    if ctx.trace is None or ctx.win is None or not ctx.trace.devices:
        return None
    tables = program_table()
    if not tables:
        return None
    dev = ctx.trace.devices[0]
    mods = [m for m in dev.modules if ctx.win[0] <= m.start < ctx.win[1]
            and m.name.split("(", 1)[0] in modules]
    if not mods:
        return None
    out = {"n": len(mods), "program": sum(m.dur for m in mods), "leaf": 0.0,
           "unmatched": 0.0, "by_scope": {}, "by_family": {}, "by_op": {},
           "unnamed": {}, "unmatched_ops": {}}
    chosen: Dict[str, Dict] = {}
    for m in mods:
        ops = [(key(e.name), e) for e in TR.within(dev.ops, m.start, m.end)
               if TR.op_kind(e.name) not in _CONTROL]
        if m.name not in chosen:
            mine = [t for mod, t in tables if mod == m.name.split("(", 1)[0]]
            chosen[m.name] = max(mine, default={}, key=lambda t: sum(
                k in t for k, _ in ops))
        table = chosen[m.name]
        for k, e in ops:
            out["leaf"] += e.dur
            seam, family = table.get(k, (None, ""))
            if seam is None:
                out["unmatched"] += e.dur
                _add(out["unmatched_ops"], TR.op_family(e.name), e.dur)
            elif not seam:
                _add(out["unnamed"], TR.op_family(e.name), e.dur)
            else:
                _add(out["by_scope"], seam, e.dur)
                if family:
                    _add(out["by_family"], f"{seam}/{family}", e.dur)
                _add(out["by_op"].setdefault(seam, {}), TR.op_family(e.name),
                     e.dur)
    return out


def per_program_ms(acc: Dict[str, float], n: int, top: int = 0) -> Dict:
    """Seconds summed over ``n`` programs -> ms a program, largest first
    (the ``top`` largest where given)."""
    rows = sorted(acc.items(), key=lambda kv: -kv[1])
    return {k: 1e3 * v / n for k, v in (rows[:top] if top else rows)}


def share(ctx, modules) -> Optional[Dict]:
    """The share of the programs' leaf-op time that the table puts under a
    seam scope, and where it goes, ms a program."""
    got = named_time(ctx, modules)
    if got is None or got["leaf"] <= 0:
        return None
    n = got["n"]
    named = sum(got["by_scope"].values())
    return {"value": 100.0 * named / got["leaf"],
            "by_scope": per_program_ms(got["by_scope"], n),
            "by_family": per_program_ms(got["by_family"], n),
            "by_op": {s: per_program_ms(ops, n, 4)
                      for s, ops in got["by_op"].items()},
            "unmatched_ms": 1e3 * got["unmatched"] / n,
            "unmatched_ops": per_program_ms(got["unmatched_ops"], n, 5),
            "unnamed_ops": per_program_ms(got["unnamed"], n, 5),
            "programs": n,
            "leaf_ms": 1e3 * got["leaf"] / n,
            "program_ms": 1e3 * got["program"] / n}
