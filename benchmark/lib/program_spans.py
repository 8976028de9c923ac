"""The program's own account of a run: its span ring, its request records and
its counters, cut to the measured window, for the readers of the
``program_span`` and ``program_counter`` metrics. With ``lib/system.py`` and
the families' adapters this is all of the benchmark that imports the program;
it takes records from it and decides nothing about them.

What the program keeps (``paddle_tpu/observability``, OBSERVABILITY.md): every
``eng.step()`` is a ``serve/step`` span with children ``serve/expire_shed``,
``serve/admit`` (under it one ``serve/prefill`` a refill, with
``serve/prefill/build|launch|wait|commit``), ``serve/ensure_blocks``,
``serve/decode`` (``serve/decode/build|checks|launch|wait|commit``) and
``serve/gauges``; every ``TrainStep.step`` is a ``step`` span with
``step/h2d``, ``step/checks``, ``step/device`` and ``step/end``; a garbage
collection is ``host/gc``; what ``jax.monitoring`` reports of a compile is
``jit/trace``, ``jit/lower``, ``jit/compile``. A record holds ``name``,
``t0_ns`` and ``dur_ns`` on ``time.perf_counter_ns``, ``id``, ``parent`` and
``tid``. That clock is the driver's own (``lib/drive.py clock``), so the
serving window ``[t_open, t_close)`` of ``ctx.run`` cuts the ring directly. A
train record holds no absolute stamp: its window's steps are the ring's last
``run["steps"]`` ``step`` spans (nothing steps after the window closes), and
"before the window" is before the first of them.

A program that keeps no such records (the ring empty under its default flags,
records without ``t0_ns``, no ``token_t_ns`` on a request, no such counter)
gives every reader nothing to read: None, never an error.

The trace's clock is another one. For a traced serving run the offset between
the two is the median of ``bench.engine_step.start - steps[i]["t0"]`` over the
traced steps (the same steps on both clocks: ``ctx.trace.spans`` and
``ctx.run["traced"]["steps"]``); the largest distance of one step's
difference from that median is reported as ``clock_residual_us``.
"""

from __future__ import annotations

import bisect
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import trace as TR
from .readers import Ctx, mean, percentile

WAITS = ("serve/prefill/wait", "serve/decode/wait")
PREFILLS = ("serve/prefill", "serve/extend", "serve/restore")
#: the parts of a ``serve/step`` that are the host's; they add up to its
#: duration less the waits inside it (``host_self``)
PARTS = ("sched", "decode_build", "decode_checks", "decode_commit", "prefill")
MIN_GAP = 2e-6      # as lib/trace.py idle_gaps cuts the device's gaps


@dataclass
class Account:
    records: List[Dict]                 # ring records that carry ``t0_ns``
    kids: Dict[int, List[Dict]]         # id -> children, by start
    self_ns: Dict[int, int]             # id -> duration less its children
    win: Tuple[int, int]                # the measured window, ring clock, ns
    traced_from: Optional[int] = None   # where the traced stretch starts
    roots: List[Dict] = field(default_factory=list)   # the window's steps

    def inside(self, rec: Dict) -> bool:
        end = rec["t0_ns"] + rec["dur_ns"]
        return self.win[0] <= end < self.win[1]


def _ring() -> List[Dict]:
    from paddle_tpu.observability import trace
    return [r for r in trace.spans() if "t0_ns" in r and "id" in r]


def request_records() -> List[Dict]:
    """The program's terminal request records that carry commit stamps."""
    from paddle_tpu.observability import request_timeline
    return [r for r in request_timeline.current().records()
            if r.get("token_t_ns") and "t_submit_ns" in r]


def counter(name: str, **labels) -> Optional[float]:
    """One series of the program's registry, as of now (a counter has no
    stamp, so it covers the process: warm-up and pre-roll too); None where
    the program has no such series."""
    from paddle_tpu.observability import metrics
    fam = metrics.snapshot().get(name)
    want = {k: str(v) for k, v in labels.items()}
    for s in (fam or {}).get("series", ()):
        if s["labels"] == want:
            return s["value"]
    return None


def self_times(records: List[Dict],
               kids: Dict[int, List[Dict]]) -> Dict[int, int]:
    """``{id: self_ns}``: a span's duration less the part of it that its
    children (``kids[id]``, by start) cover, children that overlap counted
    once; a span whose parent the ring has evicted is a root. The arithmetic
    is the benchmark's own, so that what the span metrics are computed from
    lies under the benchmark's paths."""
    out: Dict[int, int] = {}
    for r in records:
        cur, hi = r["t0_ns"], r["t0_ns"] + r["dur_ns"]
        covered = 0
        for k in kids.get(r["id"], ()):
            a, b = max(k["t0_ns"], cur), min(k["t0_ns"] + k["dur_ns"], hi)
            if b > a:
                covered += b - a
                cur = b
        out[r["id"]] = r["dur_ns"] - covered
    return out


def load(ctx: Ctx) -> Optional[Account]:
    """The ring cut to ``ctx.run``'s window; None where the ring holds none
    of the window's steps. Kept on ``ctx`` for the next reader."""
    if hasattr(ctx, "_program_account"):
        return ctx._program_account
    ctx._program_account = acc = _load(ctx)
    return acc


def _load(ctx: Ctx) -> Optional[Account]:
    records = _ring()
    if not records:
        return None
    kids: Dict[int, List[Dict]] = {}
    for r in records:
        if r.get("parent") is not None:
            kids.setdefault(r["parent"], []).append(r)
    for v in kids.values():
        v.sort(key=lambda r: r["t0_ns"])
    run = ctx.run
    if run["kind"] == "train_steps":
        steps = [r for r in records if r["name"] == "step"][-run["steps"]:]
        if len(steps) < run["steps"] or not steps:
            return None
        win = (steps[0]["t0_ns"], steps[-1]["t0_ns"] + steps[-1]["dur_ns"] + 1)
        n_traced = (run.get("traced") or {}).get("steps")
        traced_from = steps[-n_traced]["t0_ns"] if n_traced else None
    else:
        win = (int(run["t_open"] * 1e9), int(run["t_close"] * 1e9))
        tsteps = (run.get("traced") or {}).get("steps")
        traced_from = int(tsteps[0]["t0"] * 1e9) if tsteps else None
        steps = None
    acc = Account(records, kids, self_times(records, kids), win, traced_from)
    acc.roots = steps if steps is not None else [
        r for r in records if r["name"] == "serve/step" and acc.inside(r)]
    return acc if acc.roots else None


# -- serving: the host's part of each step -----------------------------------

def step_parts(acc: Account, step: Dict) -> Dict[str, float]:
    """One ``serve/step`` in ms: ``host_self`` (its duration less every wait
    inside it) and the five parts that make it up."""
    ms = dict.fromkeys(PARTS, 0.0)
    ms["sched"] = acc.self_ns[step["id"]] / 1e6
    waits = 0.0
    for c in acc.kids.get(step["id"], ()):
        dur, kids = c["dur_ns"] / 1e6, acc.kids.get(c["id"], ())
        if c["name"] == "serve/decode":
            ms["decode_build"] += acc.self_ns[c["id"]] / 1e6
            for d in kids:
                if d["name"] in WAITS:
                    waits += d["dur_ns"] / 1e6
                    continue
                part = {"serve/decode/checks": "decode_checks",
                        "serve/decode/commit": "decode_commit"}.get(
                            d["name"], "decode_build")
                ms[part] += d["dur_ns"] / 1e6
        elif c["name"] in ("serve/admit", "serve/chunk"):
            for pre in kids:
                if pre["name"] in PREFILLS:
                    wait = sum(w["dur_ns"] for w in acc.kids.get(
                        pre["id"], ()) if w["name"] in WAITS) / 1e6
                    waits += wait
                    dur -= pre["dur_ns"] / 1e6
                    ms["prefill"] += pre["dur_ns"] / 1e6 - wait
            ms["sched"] += dur
        else:       # expire_shed, ensure_blocks, gauges, a collection
            ms["sched"] += dur
    ms["host_self"] = step["dur_ns"] / 1e6 - waits
    return ms


def _plain_and_traced(acc: Account):
    """The window's steps before the traced stretch, and those inside it;
    a window that is traced from its start has only the second kind, which
    then stand for both."""
    cut = acc.traced_from
    plain = [s for s in acc.roots if cut is None or s["t0_ns"] < cut]
    traced = [s for s in acc.roots if cut is not None and s["t0_ns"] >= cut]
    return (plain or traced), traced


def per_step_ms(ctx: Ctx, part: str, by: Tuple[str, ...] = ()):
    """Mean of one part of :func:`step_parts` per ``serve/step`` of the
    window: over the steps before the traced stretch as ``value``, over the
    traced ones as ``traced_value`` (the profiler's own weight on the host
    shows between the two)."""
    acc = load(ctx)
    if acc is None or ctx.run["kind"] == "train_steps":
        return None
    plain, traced = _plain_and_traced(acc)
    parts = [step_parts(acc, s) for s in plain]
    out = {"value": mean(p[part] for p in parts), "steps": len(plain)}
    if traced:
        out["traced_value"] = mean(step_parts(acc, s)[part] for s in traced)
    if by:
        out["by_span"] = {k: mean(p[k] for p in parts) for k in by}
    return out


# -- the two clocks ----------------------------------------------------------

def clock_offset(ctx: Ctx) -> Optional[Tuple[float, float]]:
    """(offset, residual) in seconds: trace clock = ring clock + offset."""
    if ctx.trace is None or ctx.win is None or "traced" not in ctx.run:
        return None
    spans = [s for s in ctx.trace.spans if s.name == "bench.engine_step"
             and ctx.win[0] <= s.start < ctx.win[1]]
    steps = ctx.run["traced"]["steps"]
    diffs = [s.start - st["t0"] for s, st in zip(spans, steps)]
    if not diffs:
        return None
    off = statistics.median(diffs)
    return off, max(abs(d - off) for d in diffs)


def named_segments(acc: Account, lo: int, hi: int) -> List[Tuple]:
    """The thread of the window's steps between ``lo`` and ``hi`` (ring
    clock, ns) as disjoint ``(start, end, name, leaf)`` pieces: each span's
    own time, which for a span without children is all of it."""
    tid = acc.roots[0]["tid"]
    segs: List[Tuple] = []

    def walk(rec):
        a, b = rec["t0_ns"], rec["t0_ns"] + rec["dur_ns"]
        kids = acc.kids.get(rec["id"], ())
        cur = a
        for k in kids:
            ka, kb = max(k["t0_ns"], cur), min(k["t0_ns"] + k["dur_ns"], b)
            if kb <= ka:
                continue
            if ka > cur:
                segs.append((cur, ka, rec["name"], False))
            walk(dict(k, t0_ns=ka, dur_ns=kb - ka))
            cur = kb
        if b > cur:
            segs.append((cur, b, rec["name"], not kids))

    tops = [r for r in acc.records if r["tid"] == tid
            and r.get("parent") is None
            and r["t0_ns"] < hi and r["t0_ns"] + r["dur_ns"] > lo]
    for r in sorted(tops, key=lambda r: r["t0_ns"]):
        walk(r)
    segs.sort()
    return segs


def gap_named(ctx: Ctx):
    """Of the device's idle seconds inside the traced stretch (gaps between
    ``XLA Ops`` over 2 us), the share that falls inside a leaf span of the
    program after the clock mapping; ``by_span`` gives the seconds a name (a
    span's own time between its children as ``<name> (self)``, outside every
    span as ``_no_span_``; inside a wait, by the half of it the gap lies in:
    ``(head)`` the device has not started on what was launched, ``(tail)`` it
    is done and the host has not resumed)."""
    acc = load(ctx)
    off = clock_offset(ctx)
    if acc is None or off is None or not ctx.trace.devices:
        return None
    offset, residual = off
    busy = TR.union(TR.clip(TR.ivs(ctx.trace.devices[0].ops), *ctx.win))
    gaps = [(a, b) for a, b in TR.subtract([ctx.win], busy)
            if b - a >= MIN_GAP]
    if not gaps:
        return None
    to_ring = lambda t: int((t - offset) * 1e9)     # noqa: E731
    segs = named_segments(acc, to_ring(ctx.win[0]), to_ring(ctx.win[1]))
    starts = [s[0] for s in segs]
    by: Dict[str, float] = {}
    named = total = 0.0
    for a, b in gaps:
        lo, hi = to_ring(a), to_ring(b)
        total += hi - lo
        left = hi - lo
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segs) and segs[i][0] < hi:
            sa, sb, name, leaf = segs[i]
            cover = min(hi, sb) - max(lo, sa)
            if cover > 0:
                key = name if leaf else name + " (self)"
                if name in WAITS:
                    # idle while the host waits: before the device has
                    # started on what was launched, or after it is done
                    key += " (head)" if lo + hi < sa + sb else " (tail)"
                by[key] = by.get(key, 0.0) + cover / 1e9
                named += cover if leaf else 0
                left -= cover
            i += 1
        if left > 0:
            by["_no_span_"] = by.get("_no_span_", 0.0) + left / 1e9
    top = dict(sorted(by.items(), key=lambda kv: -kv[1])[:12])
    return {"value": 100.0 * named / total, "idle_s": total / 1e9,
            "by_span": top, "clock_residual_us": residual * 1e6}


# -- requests ----------------------------------------------------------------

def first_token(ctx: Ctx):
    """p95 of submit to the first token's commit, first token in the window;
    beside it the median gap from that commit to the second token's, which
    the stamp at ``eng.step()``'s return reads as 0 for a request prefilled
    and decoded in one step."""
    if ctx.run["kind"] == "train_steps":
        return None
    lo, hi = int(ctx.run["t_open"] * 1e9), int(ctx.run["t_close"] * 1e9)
    stamps = [(r["t_submit_ns"], r["token_t_ns"]) for r in request_records()
              if lo <= r["token_t_ns"][0] < hi]
    if not stamps:
        return None
    first = [(t[0] - sub) / 1e6 for sub, t in stamps]
    got = percentile(first, 95)
    if got is None:
        return None
    out = {"value": got, "samples": len(first)}
    second = [(t[1] - t[0]) / 1e6 for _, t in stamps if len(t) > 1]
    if second:
        out["second_token_gap_p50_ms"] = statistics.median(second)
    return out


def share(num: Optional[float], den: Optional[float]):
    """``100 * num / den`` of two counters; None where either is missing."""
    if num is None or not den:
        return None
    return {"value": 100.0 * num / den, "of": den}


# -- training ----------------------------------------------------------------

def train_dispatch(ctx: Ctx):
    """Mean duration of the window's ``step`` spans before the traced
    stretch (the host's cost of queueing one optimizer step), the phases
    beside it."""
    acc = load(ctx)
    if acc is None or ctx.run["kind"] != "train_steps":
        return None
    plain, traced = _plain_and_traced(acc)
    by: Dict[str, float] = {"self": mean(acc.self_ns[s["id"]] / 1e6
                                         for s in plain)}
    for s in plain:
        for k in acc.kids.get(s["id"], ()):
            short = k["name"].split("/", 1)[-1]
            by[short] = by.get(short, 0.0) + k["dur_ns"] / 1e6 / len(plain)
    out = {"value": mean(s["dur_ns"] / 1e6 for s in plain),
           "steps": len(plain), "by_span": by}
    if traced:
        out["traced_value"] = mean(s["dur_ns"] / 1e6 for s in traced)
    return out


# -- host pauses and set-up --------------------------------------------------

def gc_pause(ctx: Ctx):
    """The longest ``host/gc`` span that ended in the window; 0 when the
    collector did not run there (the program records every pass of
    generations 1 and 2, and a generation-0 pass over 0.2 ms)."""
    acc = load(ctx)
    if acc is None:
        return None
    ms = [r["dur_ns"] / 1e6 for r in acc.records
          if r["name"] == "host/gc" and acc.inside(r)]
    return {"value": max(ms, default=0.0), "count": len(ms),
            "total_ms": sum(ms)}


def setup_seconds(ctx: Ctx, names: Tuple[str, ...]):
    """Seconds that spans of ``names`` cover before the window opened (the
    union: a function traced inside another's trace is counted once)."""
    acc = load(ctx)
    if acc is None:
        return None
    got = [r for r in acc.records if r["name"] in names
           and r["t0_ns"] + r["dur_ns"] <= acc.win[0]]
    if not got:
        return None
    ivs = TR.union((r["t0_ns"], r["t0_ns"] + r["dur_ns"]) for r in got)
    return {"value": TR.total(ivs) / 1e9, "events": len(got)}
