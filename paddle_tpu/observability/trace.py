"""Span-tree tracer: host-side nested spans with structured export.

The reference merges a C++ HostTracer and a CUPTI CudaTracer into one
chrome-trace JSON (``paddle/fluid/platform/profiler/``). On TPU the device
half already exists (``jax.profiler`` XPlane); this is the host half, cheap
enough to be on in every run and structured enough to read without
TensorBoard:

- :func:`span` — thread-safe, nestable context manager. It records
  whenever ``FLAGS_telemetry != off`` (so under the default ``metrics``)
  and then also opens a ``jax.profiler.TraceAnnotation`` — a native no-op
  while no profiler session is live — so a captured XPlane carries the
  same span under the same name on the device trace's clock. With the
  flag ``off`` it returns one shared no-op object: no allocation, no
  clock read.
- a completed span is one record in a bounded ring (oldest evicted)::

      {"kind": "span", "name": "serve/decode/wait", "t0_ns": ..,
       "dur_ns": .., "id": 812, "parent": 809, "tid": .., "depth": 2,
       "attrs": {"rid": "q7"}}

  ``t0_ns``/``dur_ns`` are ``time.perf_counter_ns``; ``parent`` is the
  id of the span open on the same thread at entry (None at the root);
  request-scoped spans carry ``rid`` in ``attrs``. :func:`self_times`
  gives each span's duration less what its children cover.
- the exit of a span is the one place its duration is taken: the object
  keeps ``t0_ns``/``dur_ns`` after the ``with`` block, and callers feed
  their histograms and phase accounts from it instead of timing the same
  stretch again. :func:`timed_span` is the same span for a duration that
  *code acts on* (the shed policy's decode window): it is measured in
  every mode and recorded when telemetry is on.
- :func:`record` appends a span after the fact (a compile that
  ``jax.monitoring`` reports with its duration, a garbage collection).
- ``FLAGS_telemetry=trace`` adds the open-span table: spans entered and
  not yet exited, across all threads, which :func:`open_spans` and the
  exporters emit as ``incomplete`` — where a hung process was stuck.
- :func:`export_chrome_trace` (``chrome://tracing`` / Perfetto JSON) and
  :func:`export_jsonl` (one span per line — the format
  ``tools/trace_view.py`` aggregates).

Spans never enter traced code — a span inside ``jit`` would be a
trace-time constant; lint rule J013 flags host callbacks smuggled into
step graphs instead.
"""

from __future__ import annotations

import itertools
import json
import threading
from collections import deque
from time import perf_counter_ns
from typing import Any, Dict, Iterable, List

from ..core import flags as _flags

try:
    from jax.profiler import TraceAnnotation as _Annotation
except ImportError:                      # pragma: no cover - jax is required
    _Annotation = None

__all__ = ["span", "timed_span", "record", "annotate", "Span", "NOOP",
           "telemetry_mode", "enabled", "tracing_active", "spans",
           "open_spans", "clear", "self_times", "export_chrome_trace",
           "export_jsonl", "RING_CAPACITY"]

RING_CAPACITY = 65536

OFF, METRICS, TRACE = 0, 1, 2
_MODES = {"off": OFF, "metrics": METRICS, "trace": TRACE}

# FLAGS_telemetry as this module's own int, kept current by the flag
# registry (``set_flags`` calls back), so a span reads no flag by name
_mode = METRICS
_mode_name = "metrics"

_ring: "deque[Dict[str, Any]]" = deque(maxlen=RING_CAPACITY)
_ring_mu = threading.Lock()
_tls = threading.local()
_ids = itertools.count(1)
# trace mode only: spans entered but not yet exited, across ALL threads
_open_mu = threading.Lock()
_open: Dict[int, "Span"] = {}


def _on_flag(value: Any) -> None:
    global _mode, _mode_name
    _mode_name = str(value)
    _mode = _MODES[_mode_name]


_flags.watch("telemetry", _on_flag)


def telemetry_mode() -> str:
    """Current ``FLAGS_telemetry`` value (off | metrics | trace)."""
    return _mode_name


def enabled() -> bool:
    """Telemetry is on (``metrics`` or ``trace``): spans record."""
    return _mode != OFF


def tracing_active() -> bool:
    return _mode == TRACE


def _stack() -> List["Span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _NoopSpan:
    """What :func:`span` hands out under ``FLAGS_telemetry=off``."""

    __slots__ = ()
    t0_ns = dur_ns = end_ns = 0

    def __bool__(self) -> bool:
        # ``if sp: sp.set(...)``: attributes that cost something to work
        # out are not worked out for a span that records nothing
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs: Any) -> None:
        pass


NOOP = _NoopSpan()


class Span:
    """One span; appends its record to the ring on exit (when telemetry
    was on at entry) and keeps ``t0_ns``/``dur_ns`` for the caller."""

    __slots__ = ("name", "attrs", "t0_ns", "dur_ns", "id", "parent",
                 "depth", "tid", "_ann", "_rec")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0_ns = self.dur_ns = 0
        self.id = self.parent = None
        self.depth = self.tid = 0
        self._ann = None
        self._rec = OFF

    @property
    def end_ns(self) -> int:
        return self.t0_ns + self.dur_ns

    def set(self, **attrs: Any) -> None:
        """Attributes known only once the work is done (rows admitted)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        mode = self._rec = _mode
        if mode:
            st = _stack()
            self.depth = len(st)
            self.parent = st[-1].id if st else None
            self.id = next(_ids)
            st.append(self)
            if mode == TRACE:
                self.tid = threading.get_ident()
                with _open_mu:
                    _open[self.id] = self
            if _Annotation is not None:
                self._ann = _Annotation(self.name)
                self._ann.__enter__()
        self.t0_ns = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = perf_counter_ns() - self.t0_ns
        if not self._rec:
            return False
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        if self._rec == TRACE:
            with _open_mu:
                _open.pop(self.id, None)
        rec = {"kind": "span", "name": self.name, "t0_ns": self.t0_ns,
               "dur_ns": self.dur_ns, "id": self.id, "parent": self.parent,
               "tid": threading.get_ident(), "depth": self.depth}
        if self.attrs:
            rec["attrs"] = self.attrs
        _ring.append(rec)       # atomic; readers take the lock and retry
        return False


def span(name: str, **attrs: Any):
    """``with span("serve/prefill", rid=rid) as sp: ...`` — records unless
    ``FLAGS_telemetry=off`` (read at entry, so a runtime ``set_flags``
    takes effect at the next span)."""
    if not _mode:
        return NOOP
    return Span(name, attrs)


def timed_span(name: str, **attrs: Any) -> Span:
    """A span whose duration code acts on: measured in every mode,
    recorded like :func:`span` when telemetry is on."""
    return Span(name, attrs)


def annotate(name: str):
    """An entered ``TraceAnnotation`` for a stretch that :func:`record`
    will report afterwards (None without jax); the caller exits it."""
    if _Annotation is None:
        return None
    ann = _Annotation(name)
    ann.__enter__()
    return ann


def record(name: str, t0_ns: int, dur_ns: int, **attrs: Any) -> None:
    """Append a span that already happened (its source reports a
    duration): its parent is the span open on this thread now."""
    if not _mode:
        return
    st = _stack()
    rec = {"kind": "span", "name": name, "t0_ns": int(t0_ns),
           "dur_ns": int(dur_ns), "id": next(_ids),
           "parent": st[-1].id if st else None,
           "tid": threading.get_ident(), "depth": len(st)}
    if attrs:
        rec["attrs"] = attrs
    _ring.append(rec)


def spans() -> List[Dict[str, Any]]:
    """Snapshot of the ring (oldest first) — completed spans only; see
    :func:`open_spans` for the in-flight ones."""
    with _ring_mu:
        while True:
            try:
                return list(_ring)
            except RuntimeError:    # another thread appended meanwhile
                continue


def open_spans() -> List[Dict[str, Any]]:
    """Spans still open right now (``FLAGS_telemetry=trace`` keeps the
    table), as ``incomplete`` records whose end is the call time — a span
    that never closes is the signature of a hang, and its record says
    where."""
    now_ns = perf_counter_ns()
    with _open_mu:
        live = list(_open.values())
    out = []
    for s in live:
        rec = {"kind": "span", "name": s.name, "t0_ns": s.t0_ns,
               "dur_ns": max(0, now_ns - s.t0_ns), "id": s.id,
               "parent": s.parent, "tid": s.tid, "depth": s.depth,
               "incomplete": True}
        if s.attrs:
            rec["attrs"] = dict(s.attrs)
        out.append(rec)
    out.sort(key=lambda r: r["t0_ns"])
    return out


def clear() -> None:
    with _ring_mu:
        _ring.clear()
    with _open_mu:
        _open.clear()


def self_times(records: Iterable[Dict[str, Any]]) -> Dict[int, int]:
    """``{id: self_ns}``: each span's duration less the part of it that
    its children cover (children that overlap, as a compile reported
    inside a compile does, are counted once). A span whose parent the
    ring has evicted is a root."""
    records = list(records)
    kids: Dict[int, List] = {}
    for r in records:
        if r.get("parent") is not None:
            kids.setdefault(r["parent"], []).append(
                (r["t0_ns"], r["t0_ns"] + r["dur_ns"]))
    out: Dict[int, int] = {}
    for r in records:
        lo, hi = r["t0_ns"], r["t0_ns"] + r["dur_ns"]
        covered, cur = 0, lo
        for a, b in sorted(kids.get(r["id"], ())):
            a, b = max(a, cur), min(b, hi)
            if b > a:
                covered += b - a
                cur = b
        out[r["id"]] = r["dur_ns"] - covered
    return out


def export_chrome_trace(path: str) -> int:
    """Write the ring as chrome-trace JSON; returns the event count.
    Spans still open at export time are emitted too (end = export time,
    ``args.incomplete`` set) instead of being silently dropped."""
    events = []
    for s in spans() + open_spans():
        ev = {"name": s["name"], "ph": "X", "ts": s["t0_ns"] / 1e3,
              "dur": s["dur_ns"] / 1e3, "pid": 0, "tid": s["tid"]}
        args = dict(s.get("attrs") or {})
        if s.get("incomplete"):
            args["incomplete"] = True
        if args:
            ev["args"] = args
        events.append(ev)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)


def export_jsonl(path: str, append: bool = False) -> int:
    """Write the ring as JSONL (one span per line); returns the count.
    Open spans land flagged ``"incomplete": true`` with end = export
    time."""
    recs = spans() + open_spans()
    with open(path, "a" if append else "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return len(recs)
