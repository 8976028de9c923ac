"""What a per-layer metric's reader is handed, and the reductions that more
than one reader shares. A reader is ``read(ctx) -> number | dict | None`` in a
file of its own, ``benchmark/metrics/<metric name>.py``; None (nothing to
read) leaves the metric out of the result line.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from . import trace as TR


@dataclass
class Ctx:
    run: Dict                 # the driver's record of the window
    cfg: Dict                 # the configuration file
    mix: Dict                 # the traffic file
    cell: Dict                # the cell's entry (and its file, if any)
    chips: int
    peaks: object             # lib.peaks.Peaks of the device
    family: object = None     # lib.family.Family: ``needs`` for the counts
    trace: Optional[TR.Trace] = None
    win: Optional[TR.Interval] = None   # the traced stretch, trace clock


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (nearest rank on the sorted sample); None under
    20 values, where a 95th percentile would be a maximum."""
    if len(values) < 20:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, int(q / 100.0 * len(v)))]


def mean(values) -> Optional[float]:
    values = list(values)
    return statistics.fmean(values) if values else None


def idle_share_pct(ctx: Ctx) -> Optional[float]:
    if ctx.trace is None or ctx.win is None:
        return None
    share = TR.idle_share(ctx.trace, ctx.win)
    return None if share is None else 100.0 * share


def serve_step_programs(ctx: Ctx) -> Optional[List[Tuple[Dict, List[TR.Ev]]]]:
    """Each traced engine step's record beside the programs the device ran in
    it. The engine launches a step's prefills first and its one decode last
    and waits for each, so with the record's counts the programs need no
    names. A step whose programs do not match its record's counts is left
    out; None where there is no trace or no step lines up."""
    if ctx.trace is None or ctx.win is None or "traced" not in ctx.run:
        return None
    spans = TR.modules_in_spans(ctx.trace, "bench.engine_step", ctx.win)
    out = []
    for (_, mods), st in zip(spans, ctx.run["traced"]["steps"]):
        want = len(st["prefills"]) + (1 if st["decode_ctx"] else 0)
        if len(mods) == want:
            out.append((st, mods))
    return out or None


def decode_programs(ctx: Ctx):
    sp = serve_step_programs(ctx)
    if sp is None:
        return None
    return [(st, mods[-1]) for st, mods in sp if st["decode_ctx"]]


def prefill_programs(ctx: Ctx):
    sp = serve_step_programs(ctx)
    if sp is None:
        return None
    return [m for st, mods in sp for m in mods[:len(st["prefills"])]]
