"""Step timeline, recompile sentinel, and HBM watermarks.

The always-on measurement layer for the training hot path. Three signals,
all host-side (nothing here touches traced code — outputs are bitwise
identical under every ``FLAGS_telemetry`` mode):

**StepTimeline** — per-step phase accounting. ``framework.sharded.
TrainStep``, ``framework.offload.StreamingUpdate``, ``distributed.
pipeline_schedule``, ``distributed.overlap`` (dispatch-level bucketed
gradient reductions), ``io.dataloader`` and the ``hapi`` fit loop report
into the phases (``data``, ``h2d``, ``compile``, ``device``, ``comm``,
``offload_in``, ``offload_out``, ``callbacks``); each completed step is a
record in a bounded ring (with the step's ``t0_ns`` on
``time.perf_counter_ns``), and every step and phase is a :mod:`.trace` span
(``step``, ``step/<phase>``, ``step/end``) whose exit is where the duration
is taken: it feeds the record and the log-bucket histograms in
:mod:`.metrics`. ``tools/trace_view.py`` aggregates the JSONL export.

**RecompileSentinel** — the silent step-time killer on XLA is shape churn:
a jitted callable fed a new (shape, dtype, sharding) signature recompiles,
and nothing says so. Every instrumented dispatch fingerprints its abstract
signature; when one callable accumulates more than N distinct fingerprints
the sentinel raises a :class:`~paddle_tpu.analysis.Diagnostic` (rule O001)
through the existing analysis channel, reporting the exact leaf-level
shape/dtype diff between the two most recent signatures — the reference's
``nan_inf``-style always-on guard, aimed at compilation instead.

**HBM watermarks** — ``device.memory_stats()`` sampled at every step end
(live + peak bytes into gauges, process peak tracked), cross-checkable
against the static plan from ``tools/hbm_budget.py`` via
:meth:`StepTimeline.check_plan` (rule O002 when measured peak exceeds the
plan). On CPU ``memory_stats()`` is None and sampling degrades to a no-op.

**Host hooks** — :func:`install_host_hooks` (called once when this module is
imported) gives the span ring the two things that stall a host thread from
outside the program's own code: every compile ``jax.monitoring`` reports
becomes a ``jit/trace``, ``jit/lower`` or ``jit/compile`` span (and feeds the
``jit.compiles`` / ``jit.cache_hits`` counters — the program's own compile
counter), and garbage collections become ``host/gc`` spans.
"""

from __future__ import annotations

import gc
import json
import threading
from collections import deque
from time import perf_counter_ns
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import flight_recorder, metrics, trace

__all__ = ["StepTimeline", "RecompileSentinel", "current", "reset_default",
           "fingerprint", "fingerprint_diff", "instrument_jitted",
           "install_host_hooks", "PHASES", "GB", "GC_GEN0_MIN_NS"]

PHASES = ("data", "h2d", "checks", "compile", "device", "comm",
          "ckpt_save", "ckpt_restore", "offload_in",
          "offload_out", "callbacks",
          # training-health tier (fault/health.py): the SDC canary's
          # double-execution window and the guardian's rewind restore
          "canary", "rewind")

GB = float(2 ** 30)

# Distinct compile fingerprints one callable may accumulate before the
# sentinel fires: 1 is the expected compile, 2 tolerates a one-off second
# signature (e.g. a short final batch); the 3rd distinct signature is churn.
DEFAULT_RECOMPILE_THRESHOLD = 2


# ---------------------------------------------------------------------------
# Abstract-signature fingerprinting
# ---------------------------------------------------------------------------

def _leaf_desc(x) -> Tuple[str, str, str]:
    """(shape, dtype, sharding/memory-kind) of one pytree leaf — the parts
    of the abstract signature a retrace keys on."""
    shape = "x".join(str(int(d)) for d in getattr(x, "shape", ()) or ())
    dtype = str(getattr(x, "dtype", type(x).__name__))
    sh = getattr(x, "sharding", None)
    place = ""
    if sh is not None:
        try:
            spec = getattr(sh, "spec", None)
            kind = getattr(sh, "memory_kind", None)
            place = f"{spec if spec is not None else ''}" + \
                (f"@{kind}" if kind else "")
        except Exception:
            place = ""
    return (shape, dtype, place)


def fingerprint(tree: Any, donate: Sequence[int] = ()) -> Tuple:
    """Hashable signature of a pytree: per-leaf (path, shape, dtype,
    sharding) plus the donation config — what a jitted callable's
    executable cache keys on, minus the weak-type minutiae."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return (tuple(donate),) + tuple(
        (jax.tree_util.keystr(path),) + _leaf_desc(leaf)
        for path, leaf in flat)


def fingerprint_fast(tree: Any) -> Tuple:
    """Cheap per-dispatch signature: (treedef, per-leaf shape+dtype). No
    path strings, no ``.sharding`` property access (both are an order of
    magnitude more expensive than the dispatch itself) — the sentinel
    computes the full :func:`fingerprint` only when this one is new. A
    resharding that changes neither shape nor dtype is the one signature
    change this tier cannot see."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef,) + tuple(
        (getattr(leaf, "shape", None), getattr(leaf, "dtype", None))
        for leaf in flat)


def fingerprint_diff(old: Tuple, new: Tuple) -> str:
    """Human-readable leaf-level diff between two fingerprints — the
    shape/dtype change that caused a recompile."""
    o_by = {e[0]: e[1:] for e in old[1:]}
    n_by = {e[0]: e[1:] for e in new[1:]}
    parts: List[str] = []
    if old[0] != new[0]:
        parts.append(f"donate {old[0]} -> {new[0]}")
    for key in sorted(set(o_by) | set(n_by)):
        a, b = o_by.get(key), n_by.get(key)
        if a == b:
            continue
        def fmt(d):
            if d is None:
                return "<absent>"
            shape, dtype, place = d
            return f"{dtype}[{shape.replace('x', ',')}]" + \
                (f"@{place}" if place else "")
        parts.append(f"{key or '<root>'}: {fmt(a)} -> {fmt(b)}")
    return "; ".join(parts) if parts else "<identical signatures>"


# ---------------------------------------------------------------------------
# Recompile sentinel
# ---------------------------------------------------------------------------

class RecompileSentinel:
    """Counts distinct abstract signatures per jitted callable; fires one
    Diagnostic (rule O001, via the analysis channel) per callable when the
    count exceeds the threshold."""

    def __init__(self, threshold: int = DEFAULT_RECOMPILE_THRESHOLD):
        self.threshold = threshold
        self._mu = threading.Lock()
        self._seen: Dict[Any, List[Tuple]] = {}
        self._fast: Dict[Any, set] = {}
        self._fired: set = set()
        self.diagnostics: List[Any] = []

    def observe_tree(self, key: Any, tree: Any, donate: Sequence[int] = (),
                     where: str = "") -> bool:
        """Two-tier :meth:`observe`: the cheap fingerprint gates the full
        one, so the steady state (signature already seen) costs a couple
        of microseconds. Returns True when the signature is new."""
        fast = fingerprint_fast(tree)
        with self._mu:
            seen = self._fast.setdefault(key, set())
            if fast in seen:
                return False
            seen.add(fast)
        return self.observe(key, fingerprint(tree, donate), where)

    def observe(self, key: Any, fp: Tuple, where: str = "") -> bool:
        """Record one dispatch. Returns True when `fp` is NEW for `key`
        (i.e. this dispatch pays a compile)."""
        with self._mu:
            fps = self._seen.setdefault(key, [])
            if fp in fps:
                return False
            fps.append(fp)
            n = len(fps)
            fire = n > self.threshold and key not in self._fired
            if fire:
                self._fired.add(key)
            prev = fps[-2] if n >= 2 else None
        metrics.counter(
            "telemetry.compiles",
            "distinct jit signatures observed per callable").labels(
                fn=str(where or key)).inc()
        if fire:
            self._emit(key, where, n, prev, fp)
        return True

    def _emit(self, key, where, n, prev, fp) -> None:
        from ..analysis import jaxpr_lint
        d = jaxpr_lint.Diagnostic(
            rule="O001", name="recompile-churn",
            severity=jaxpr_lint.WARNING,
            message=(f"callable compiled {n} times with differing "
                     f"signatures (threshold {self.threshold}); last "
                     f"change: {fingerprint_diff(prev, fp)}"),
            where=where or str(key),
            hint="pad/bucket inputs to a fixed shape set, or mark the "
                 "varying operand static — every new signature pays a "
                 "full XLA compile")
        with self._mu:   # reset() swaps the list under the same lock
            self.diagnostics.append(d)
        flight_recorder.emit("diag", rule=d.rule, where=d.where,
                             message=d.message)
        try:
            jaxpr_lint.emit([d], where=d.where)
        except jaxpr_lint.GraphLintError:
            raise
        except Exception:
            pass

    def reset(self) -> None:
        with self._mu:
            self._seen.clear()
            self._fast.clear()
            self._fired.clear()
            self.diagnostics = []


# ---------------------------------------------------------------------------
# Step timeline
# ---------------------------------------------------------------------------

class _Phase:
    __slots__ = ("_tl", "name", "_span")

    def __init__(self, tl: "StepTimeline", name: str, attrs: Dict[str, Any]):
        self._tl = tl
        self.name = name
        self._span = trace.span(f"step/{name}", **attrs)

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._tl._phase_done(self.name, self._span.dur_ns / 1e6)
        return False


class _Step:
    __slots__ = ("_tl", "_span")

    def __init__(self, tl: "StepTimeline"):
        self._tl = tl
        self._span = None

    def __enter__(self):
        self._span = trace.span("step", step=self._tl._step_begin())
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        # the step's wall time ends where its own bookkeeping starts: the
        # record, the HBM sample and the recorder commit are ``step/end``,
        # inside the root, so the root's self time is what no phase names
        with trace.span("step/end") as end:
            self._tl._step_end(self._span.t0_ns, end.t0_ns)
        self._span.__exit__(*exc)
        return False


class StepTimeline:
    """Per-step phase timeline + recompile sentinel + HBM watermarks.

    All methods are cheap no-ops under ``FLAGS_telemetry=off``; the flag is
    re-read at every step/phase entry so runtime ``set_flags`` changes take
    effect immediately.
    """

    def __init__(self, capacity: int = 4096,
                 recompile_threshold: int = DEFAULT_RECOMPILE_THRESHOLD,
                 device: Any = None):
        self._mu = threading.RLock()
        self._steps: "deque[Dict[str, Any]]" = deque(maxlen=capacity)
        self._cur: Optional[Dict[str, Any]] = None
        self._step_idx = 0
        self._device = device
        self.sentinel = RecompileSentinel(recompile_threshold)
        self.hbm_peak_bytes = 0
        self.hbm_live_bytes = 0
        self.diagnostics: List[Any] = []
        # hot-path metric children resolved once (registry + label lookups
        # off the per-phase path)
        self._phase_hists: Dict[str, Any] = {}
        self._hbm_live = metrics.gauge(
            "hbm.bytes_in_use", "live device bytes").labels()
        self._hbm_peak = metrics.gauge(
            "hbm.peak_bytes_in_use", "runtime peak device bytes").labels()

    # -- gating --------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return trace.enabled()

    # -- step / phase context managers --------------------------------------

    def step(self):
        """``with timeline.step(): ...`` around one training step."""
        if not self.enabled:
            return trace.NOOP
        return _Step(self)

    def phase(self, name: str, **attrs):
        """``with timeline.phase("h2d"): ...``; durations accumulate into
        the current step record (or stand alone between steps) and feed
        the ``telemetry.phase_ms`` histogram."""
        if not self.enabled:
            return trace.NOOP
        return _Phase(self, name, attrs)

    def note(self, key: str, value: Any) -> None:
        """Annotate the OPEN step record (no-op between steps / off).
        ``sharded.TrainStep`` notes its applied-step ``index`` here so
        the flight recorder's step commits carry the trainer's global
        step, not just the timeline's incarnation-local count."""
        if not self.enabled:
            return
        with self._mu:
            if self._cur is not None:
                self._cur[key] = value

    def _step_begin(self) -> int:
        with self._mu:
            self._step_idx += 1
            self._cur = {"kind": "step", "step": self._step_idx, "phases": {}}
            return self._step_idx

    def _step_end(self, t0_ns: int, end_ns: int) -> None:
        hbm = self.sample_hbm()
        with self._mu:
            cur = self._cur
            self._cur = None
        if cur is None:
            return
        cur["t0_ns"] = t0_ns
        cur["total_ms"] = (end_ns - t0_ns) / 1e6
        if hbm is not None:
            cur["hbm_live_gb"] = round(hbm["bytes_in_use"] / GB, 4)
            cur["hbm_peak_gb"] = round(hbm["peak_bytes_in_use"] / GB, 4)
        with self._mu:
            self._steps.append(cur)
        # black-box commit: the step's phase totals land in the
        # crash-persistent ring the moment the record returns, so a
        # SIGKILL in the very next instruction keeps this step
        flight_recorder.emit(
            "step", step=cur["step"], index=cur.get("index"),
            total_ms=round(cur["total_ms"], 4),
            phases={k: round(v, 4) for k, v in cur["phases"].items()},
            **({"hbm_peak_gb": cur["hbm_peak_gb"]}
               if "hbm_peak_gb" in cur else {}))
        flight_recorder.maybe_metrics(cur.get("index", cur["step"]))

    def _phase_done(self, name: str, dur_ms: float) -> None:
        with self._mu:
            standalone = self._cur is None
            if self._cur is not None:
                ph = self._cur["phases"]
                ph[name] = ph.get(name, 0.0) + dur_ms
            hist = self._phase_hists.get(name)
            if hist is None:
                hist = self._phase_hists[name] = metrics.histogram(
                    "telemetry.phase_ms",
                    "wall time per step phase (ms)").labels(phase=name)
        hist.observe(dur_ms)
        if standalone:
            # between-steps phases (ckpt_restore, the guardian's rewind)
            # are exactly the recovery work a postmortem reconstructs
            flight_recorder.emit("phase", phase=name,
                                 ms=round(dur_ms, 4))

    # -- dispatch observation (sentinel + compile attribution) ---------------

    def observe_dispatch(self, key: Any, tree: Any,
                         donate: Sequence[int] = (), where: str = "") -> str:
        """Feed one dispatch's argument pytree to the sentinel; returns
        the phase name the dispatch should be timed under ("compile" the
        first time a signature is seen, "device" after)."""
        return "compile" if self.sentinel.observe_tree(key, tree, donate,
                                                       where) else "device"

    # -- HBM watermarks ------------------------------------------------------

    def _default_device(self):
        if self._device is None:
            try:
                import jax
                self._device = jax.devices()[0]
            except Exception:
                return None
        return self._device

    def sample_hbm(self) -> Optional[Dict[str, int]]:
        """One ``memory_stats()`` sample -> gauges + process peak; None on
        runtimes without memory stats (CPU)."""
        dev = self._default_device()
        if dev is None:
            return None
        try:
            ms = dev.memory_stats()
        except Exception:
            return None
        if not ms:
            return None
        live = int(ms.get("bytes_in_use", 0))
        peak = int(ms.get("peak_bytes_in_use", live))
        with self._mu:
            self.hbm_live_bytes = live
            self.hbm_peak_bytes = max(self.hbm_peak_bytes, peak, live)
        self._hbm_live.set(live)
        self._hbm_peak.set(max(self.hbm_peak_bytes, peak))
        return {"bytes_in_use": live, "peak_bytes_in_use": peak}

    def check_plan(self, plan: Dict[str, Any], slack: float = 0.05):
        """Cross-check the measured HBM peak against a static plan from
        ``tools/hbm_budget.py`` (a ``gpt_plan``-style dict with
        ``device_gb``). Returns the O002 Diagnostic when the measured peak
        exceeds the plan by more than ``slack`` (and routes it through the
        analysis channel), else None."""
        planned_gb = float(plan.get("device_gb", 0.0))
        if not planned_gb or not self.hbm_peak_bytes:
            return None
        measured_gb = self.hbm_peak_bytes / GB
        if measured_gb <= planned_gb * (1.0 + slack):
            return None
        from ..analysis import jaxpr_lint
        d = jaxpr_lint.Diagnostic(
            rule="O002", name="hbm-plan-exceeded",
            severity=jaxpr_lint.WARNING,
            message=(f"measured HBM peak {measured_gb:.2f} GB exceeds the "
                     f"static plan's {planned_gb:.2f} GB "
                     f"(+{100 * (measured_gb / planned_gb - 1):.1f}%)"),
            where="observability.step_monitor",
            hint="the tools/hbm_budget.py accounting is missing a row "
                 "(new activation, fragmentation, an un-donated buffer) — "
                 "update the plan or find the leak")
        with self._mu:   # reset() swaps the list under the same lock
            self.diagnostics.append(d)
        flight_recorder.emit("diag", rule=d.rule, where=d.where,
                             message=d.message)
        try:
            jaxpr_lint.emit([d], where=d.where)
        except jaxpr_lint.GraphLintError:
            raise
        except Exception:
            pass
        return d

    # -- inspection / export -------------------------------------------------

    def steps(self) -> List[Dict[str, Any]]:
        with self._mu:
            return list(self._steps)

    def all_diagnostics(self) -> List[Any]:
        return list(self.sentinel.diagnostics) + list(self.diagnostics)

    def summary(self) -> Dict[str, Any]:
        """Per-phase aggregate over the recorded steps."""
        steps = self.steps()
        phases: Dict[str, Dict[str, float]] = {}
        for s in steps:
            for name, ms in s.get("phases", {}).items():
                agg = phases.setdefault(
                    name, {"calls": 0, "total_ms": 0.0, "max_ms": 0.0})
                agg["calls"] += 1
                agg["total_ms"] += ms
                agg["max_ms"] = max(agg["max_ms"], ms)
        for agg in phases.values():
            agg["avg_ms"] = agg["total_ms"] / max(agg["calls"], 1)
        totals = [s["total_ms"] for s in steps if "total_ms" in s]
        return {
            "steps": len(steps),
            "phases": {k: {kk: (round(vv, 4) if isinstance(vv, float) else vv)
                           for kk, vv in v.items()}
                       for k, v in sorted(phases.items())},
            "avg_step_ms": round(sum(totals) / len(totals), 4)
            if totals else None,
            "hbm_peak_gb": round(self.hbm_peak_bytes / GB, 4)
            if self.hbm_peak_bytes else None,
            "recompile_diagnostics": len(self.sentinel.diagnostics),
        }

    def export_jsonl(self, path: str, append: bool = False) -> int:
        """One JSON record per step (the ``tools/trace_view.py`` input);
        returns the record count."""
        steps = self.steps()
        with open(path, "a" if append else "w") as f:
            for s in steps:
                f.write(json.dumps(s) + "\n")
        return len(steps)

    def reset(self) -> None:
        with self._mu:
            self._steps.clear()
            self._cur = None
            self._step_idx = 0
            self.hbm_peak_bytes = 0
            self.hbm_live_bytes = 0
            self.diagnostics = []
        self.sentinel.reset()


# ---------------------------------------------------------------------------
# Process-wide default timeline
# ---------------------------------------------------------------------------

_default: Optional[StepTimeline] = None
_default_mu = threading.Lock()


def current() -> StepTimeline:
    """The process-wide timeline every instrumented subsystem reports to."""
    global _default
    tl = _default
    if tl is None:
        with _default_mu:
            if _default is None:
                _default = StepTimeline()
            tl = _default
    return tl


def reset_default() -> StepTimeline:
    """Fresh default timeline (tests / run boundaries)."""
    global _default
    with _default_mu:
        _default = StepTimeline()
        return _default


# ---------------------------------------------------------------------------
# Host hooks: compiles and garbage collections as spans
# ---------------------------------------------------------------------------

_JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # the backend compile, or the read of it from the persistent cache
    "/jax/core/compile/backend_compile_duration": "compile",
}
_JIT_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: A generation-0 collection shorter than this leaves no span: tracing a
#: model makes tens of thousands of them, 10-50 us each, and they would
#: push the run's other spans out of the ring. Generations 1 and 2, and
#: any longer pass, always leave one.
GC_GEN0_MIN_NS = 200_000

#: A ``jit/trace`` event shorter than this leaves no span, for the same
#: reason: tracing a 12-layer step reports some 18,000 of them, nearly all
#: the small functions traced inside a larger trace, whose span covers them.
JIT_TRACE_MIN_NS = 1_000_000

_hooks_mu = threading.Lock()
_hooks_on = False
_gc_open: Optional[Tuple[int, Any]] = None     # (t0_ns, annotation)


def _on_jit_event(event: str, duration: float, **kw: Any) -> None:
    if not trace.enabled():
        return
    stage = _JIT_STAGES.get(event)
    if stage is None:
        if event == _JIT_CACHE_HIT:
            metrics.counter(
                "jit.cache_hits",
                "programs read from the persistent compile cache").inc()
        return
    dur_ns = int(duration * 1e9)
    if stage == "trace" and dur_ns < JIT_TRACE_MIN_NS:
        return
    fn = kw.get("fun_name")
    trace.record(f"jit/{stage}", perf_counter_ns() - dur_ns, dur_ns,
                 event=event, **({"fn": str(fn)} if fn else {}))
    if stage == "compile":
        metrics.counter("jit.compiles",
                        "programs compiled or read from the compile "
                        "cache").inc()


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    global _gc_open
    if phase == "start":
        if trace.enabled():
            ann = trace.annotate("host/gc")
            _gc_open = (perf_counter_ns(), ann)
        return
    opened, _gc_open = _gc_open, None
    if opened is None:
        return
    t0, ann = opened
    dur = perf_counter_ns() - t0
    if ann is not None:
        ann.__exit__(None, None, None)
    if info.get("generation", 0) > 0 or dur >= GC_GEN0_MIN_NS:
        trace.record("host/gc", t0, dur,
                     generation=info.get("generation"),
                     collected=info.get("collected"))


def install_host_hooks() -> None:
    """Register the ``jax.monitoring`` duration listener and the
    ``gc.callbacks`` hook, once a process. Both return at once under
    ``FLAGS_telemetry=off``."""
    global _hooks_on
    with _hooks_mu:
        if _hooks_on:
            return
        _hooks_on = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_jit_event)
    gc.callbacks.append(_on_gc)


install_host_hooks()


# ---------------------------------------------------------------------------
# Generic jitted-callable instrumentation
# ---------------------------------------------------------------------------

def instrument_jitted(fn, name: Optional[str] = None,
                      timeline: Optional[StepTimeline] = None,
                      donate: Sequence[int] = ()):
    """Wrap a jitted callable: each call is fingerprinted through the
    recompile sentinel and timed under the "compile" (first time a
    signature is seen) or "device" phase. AOT attributes (``lower``,
    ``trace``) pass through so compiled-cost introspection keeps working.
    Zero-added-behavior under ``FLAGS_telemetry=off``."""
    label = name or getattr(fn, "__name__", "jitted")
    key = (label, id(fn))

    def wrapper(*args, **kwargs):
        tl = timeline if timeline is not None else current()
        if not tl.enabled:
            return fn(*args, **kwargs)
        ph = tl.observe_dispatch(key, (args, kwargs), donate=donate,
                                 where=label)
        with tl.phase(ph, fn=label):
            return fn(*args, **kwargs)

    wrapper.__name__ = label
    wrapper.__wrapped__ = fn
    for attr in ("lower", "trace", "eval_shape"):
        if hasattr(fn, attr):
            setattr(wrapper, attr, getattr(fn, attr))
    return wrapper
