"""From a profiler trace (``.xplane.pb``) to intervals, and from intervals to
busy time, per-program time and named idle gaps. Read with
``jax.profiler.ProfileData`` alone.

What this chip writes (TPU v5e, libtpu 0.0.34): a plane ``/device:TPU:<n>`` a
chip with the lines ``XLA Modules`` (one event per executed program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per executed HLO
instruction, named by the instruction's text ``%name = shape op(...)``);
``Async XLA Ops`` holds DMA that overlaps compute and is not busy time. The
plane ``/host:CPU`` has a line ``python`` on which ``TraceAnnotation`` spans
appear under their own names. Host and device events share one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")
_OP_RE = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)
_KIND_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclass
class Ev:
    name: str
    start: float   # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    name: str
    ops: List[Ev] = field(default_factory=list)
    modules: List[Ev] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Ev]      # the benchmark's own host spans (``bench.*``)

    def window(self) -> Optional[Interval]:
        """The ``bench.window`` span: the traced stretch of the window."""
        for s in self.spans:
            if s.name == "bench.window":
                return (s.start, s.end)
        return None


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    dev.ops = [Ev(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9) for e in ln.events]
                elif ln.name == "XLA Modules":
                    dev.modules = [Ev(e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9)
                                   for e in ln.events]
            dev.ops.sort(key=lambda e: e.start)
            dev.modules.sort(key=lambda e: e.start)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append(Ev(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, spans)


# -- names ---------------------------------------------------------------------

def op_name(text: str) -> str:
    """``%fusion.18 = ...`` -> ``fusion.18``."""
    m = _OP_RE.match(text)
    return m.group("name") if m else text


def op_family(text: str) -> str:
    """``%slice_bitcast_fusion.7 = ...`` -> ``slice_bitcast_fusion``."""
    return re.sub(r"\.\d+$", "", op_name(text))


def op_kind(text: str) -> str:
    """The HLO opcode: ``fusion``, ``custom-call``, ``all-reduce``..."""
    m = _OP_RE.match(text)
    if not m:
        return ""
    k = _KIND_RE.search(" " + m.group("rest"))
    return k.group(1) if k else ""


def is_collective(text: str) -> bool:
    kind = op_kind(text)
    return any(kind == c or kind.startswith(c + "-") for c in COLLECTIVE_KINDS)


def is_pallas_call(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


# -- intervals -------------------------------------------------------------------

def clip(ivs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in ivs
            if min(b, hi) > max(a, lo)]


def union(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union ``a`` that no interval of union ``b`` covers."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def ivs(events: Iterable[Ev]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def within(events: Sequence[Ev], lo: float, hi: float) -> List[Ev]:
    """Events (sorted by start) that start inside [lo, hi)."""
    i = bisect.bisect_left(events, lo, key=lambda e: e.start)
    j = bisect.bisect_left(events, hi, key=lambda e: e.start)
    return list(events[i:j])


# -- reductions --------------------------------------------------------------------

def busy_seconds(tr: Trace, win: Interval) -> float:
    """Seconds in which an operation ran on the device, inside ``win``,
    averaged over the devices that ran anything."""
    per = [total(union(clip(ivs(d.ops), *win))) for d in tr.devices]
    per = [p for p in per if p > 0]
    return sum(per) / len(per) if per else 0.0


def idle_share(tr: Trace, win: Interval) -> Optional[float]:
    if win[1] <= win[0]:
        return None
    return 1.0 - busy_seconds(tr, win) / (win[1] - win[0])


def program_times(dev: Device, win: Optional[Interval] = None
                  ) -> Dict[str, List[float]]:
    """Device seconds of each executed program, by program name."""
    out: Dict[str, List[float]] = {}
    for m in dev.modules:
        if win is None or (win[0] <= m.start and m.end <= win[1]):
            out.setdefault(m.name, []).append(m.dur)
    return out


def main_program(dev: Device, win: Optional[Interval] = None
                 ) -> Tuple[Optional[str], List[float]]:
    """The program that took most device time (a cell's step program)."""
    pt = program_times(dev, win)
    if not pt:
        return None, []
    name = max(pt, key=lambda k: sum(pt[k]))
    return name, pt[name]


def top_ops(tr: Trace, win: Interval, n: int = 10) -> List[List]:
    """The op families that took most device time on the first device."""
    if not tr.devices:
        return []
    acc: Dict[str, float] = {}
    for e in tr.devices[0].ops:
        if win[0] <= e.start < win[1]:
            fam = op_family(e.name)
            acc[fam] = acc.get(fam, 0.0) + e.dur
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, win: Interval, n: int = 10,
              min_gap: float = 2e-6) -> List[List]:
    """The device's idle time inside ``win`` by what the host was doing: each
    gap between device operations goes to the benchmark span (other than the
    window's own) that covers most of it, ``_no_span_`` where none does; the
    gaps under ``min_gap`` (one op handing over to the next) are summed as
    ``_between_ops_``."""
    if not tr.devices:
        return []
    busy = union(clip(ivs(tr.devices[0].ops), *win))
    spans = [s for s in tr.spans if s.name != "bench.window"]
    ends = [s.end for s in spans]   # spans of one thread: sorted by end too
    acc: Dict[str, float] = {}
    for lo, hi in subtract([win], busy):
        best, best_cover = "_no_span_", 0.0
        if hi - lo < min_gap:
            best = "_between_ops_"
        else:
            i = bisect.bisect_right(ends, lo)
            while i < len(spans) and spans[i].start < hi:
                cover = min(hi, spans[i].end) - max(lo, spans[i].start)
                if cover > best_cover:
                    best, best_cover = spans[i].name, cover
                i += 1
        acc[best] = acc.get(best, 0.0) + (hi - lo)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(dev: Device, win: Interval) -> float:
    """Seconds inside ``win`` in which a collective ran on ``dev`` and no
    other operation did."""
    ops = [e for e in dev.ops if win[0] <= e.start < win[1]]
    coll = union(ivs(e for e in ops if is_collective(e.name)))
    # a control-flow op spans its body; only leaf ops count as compute
    comp = union(ivs(e for e in ops if not is_collective(e.name)
                     and op_kind(e.name) not in ("while", "conditional",
                                                 "call")))
    return total(subtract(coll, comp))


def modules_in_spans(tr: Trace, span_name: str, win: Interval,
                     min_dur: float = 50e-6) -> List[Tuple[Ev, List[Ev]]]:
    """For each ``span_name`` host span that starts inside ``win``: the
    programs (of at least ``min_dur``) the first device ran while the host
    was in it. The engine waits for each program it launches, so a program
    runs inside the span that launched it."""
    if not tr.devices:
        return []
    mods = [m for m in tr.devices[0].modules if m.dur >= min_dur]
    out = []
    for s in tr.spans:
        if s.name == span_name and win[0] <= s.start < win[1]:
            out.append((s, within(mods, s.start, s.end)))
    return out
