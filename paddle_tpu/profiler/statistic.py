"""Profiler statistics summarizer (ref python/paddle/profiler/
profiler_statistic.py:1 — the per-op/per-view aggregate report printed by
``Profiler.summary()``).

Two sources feed the report:

- **host events**: ``RecordEvent`` spans recorded by this process (the
  reference's HostTracer analog) — aggregated per name into calls/total/
  avg/max/min + share of wall time;
- **device stats**: the XPlane protobuf captured by ``jax.profiler`` into
  the profiler's ``log_dir`` (the reference's CUPTI/ChromeTracingLogger
  analog). Parsed with the xprof converter when available — per-HLO-
  category device time plus a top-ops table (the KernelView).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["host_statistics", "device_statistics", "device_total_ms",
           "summary_report", "EventStat"]


class EventStat:
    __slots__ = ("name", "calls", "total_ns", "max_ns", "min_ns")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.total_ns = 0
        self.max_ns = 0
        self.min_ns = 1 << 62

    def add(self, dur_ns: int):
        self.calls += 1
        self.total_ns += dur_ns
        self.max_ns = max(self.max_ns, dur_ns)
        self.min_ns = min(self.min_ns, dur_ns)

    @property
    def avg_ns(self):
        return self.total_ns / max(self.calls, 1)


def host_statistics(events: Optional[Sequence[Tuple[str, int, int]]] = None
                    ) -> List[EventStat]:
    """Aggregate (name, begin_ns, end_ns) spans per name, sorted by total
    time descending (ref profiler_statistic HostStatisticNode roll-up)."""
    if events is None:
        from . import _host_events
        events = _host_events
    stats: Dict[str, EventStat] = {}
    for name, b, e in events:
        stats.setdefault(name, EventStat(name)).add(e - b)
    return sorted(stats.values(), key=lambda s: -s.total_ns)


def _degrade(message: str, severity: Optional[str] = None,
             diagnostics=None) -> None:
    """Record a structured note about why device stats are unavailable and
    route it through the analysis channel (rule O003). Never raises: a
    missing/broken profile dump must degrade the report, not the run."""
    try:
        from ..analysis import jaxpr_lint
        d = jaxpr_lint.Diagnostic(
            rule="O003", name="device-stats-unavailable",
            severity=severity or jaxpr_lint.INFO, message=message,
            where="profiler.statistic.device_statistics",
            hint="host-side stats still work; re-capture the trace (or "
                 "install xprof/tensorboard_plugin_profile) for the "
                 "KernelView")
        if diagnostics is not None:
            diagnostics.append(d)
        try:
            jaxpr_lint.emit([d], where=d.where)
        except jaxpr_lint.GraphLintError:
            raise
        except Exception:
            pass
    except ImportError:
        pass


def device_statistics(log_dir: str, top: int = 15, diagnostics=None):
    """Parse the newest xplane.pb under log_dir into (by_category,
    top_ops). Degrades gracefully — returns None (with an O003 Diagnostic
    through the analysis channel, appended to ``diagnostics`` when a list
    is given) when no parser is importable, the log dir is missing/empty,
    or the XPlane payload is unparseable. Never raises."""
    try:
        from xprof.convert import raw_to_tool_data as rtd
    except Exception:
        # tensorboard_plugin_profile can fail with AttributeError (its
        # _pywrap_profiler ABI drifts), not just ImportError — any failure
        # to produce a parser degrades the same way.
        try:
            from tensorboard_plugin_profile.convert import (  # type: ignore
                raw_to_tool_data as rtd)
        except Exception as e:
            _degrade(f"no usable XPlane parser: {type(e).__name__}: {e}",
                     diagnostics=diagnostics)
            return None
    if not os.path.isdir(log_dir):
        _degrade(f"profiler log dir {log_dir!r} does not exist",
                 diagnostics=diagnostics)
        return None
    sessions = sorted(glob.glob(os.path.join(log_dir, "plugins/profile/*")))
    if not sessions:
        _degrade(f"no profile sessions under {log_dir!r}",
                 diagnostics=diagnostics)
        return None
    xplane = glob.glob(os.path.join(sessions[-1], "*.xplane.pb"))
    if not xplane:
        _degrade(f"no *.xplane.pb in session {sessions[-1]!r}",
                 diagnostics=diagnostics)
        return None
    try:
        import json
        data, _ = rtd.xspace_to_tool_data(xplane, "hlo_stats", {})
        d = json.loads(data.decode() if isinstance(data, bytes) else data)
        cols = [c["id"] for c in d["cols"]]
        rows = [[c.get("v") for c in r["c"]] for r in d["rows"]]

        def col(name):
            return cols.index(name) if name in cols else None

        i_cat, i_t = col("category"), col("total_self_time")
        i_expr = col("hlo_op_expression") or col("hlo_op_name")
        i_bound = col("bound_by")
        i_occ = col("occurrences")
        by_cat: Dict[str, float] = {}
        for r in rows:
            t = (r[i_t] or 0.0) / 1e3  # us -> ms
            by_cat[str(r[i_cat])] = by_cat.get(str(r[i_cat]), 0.0) + t
        rows.sort(key=lambda r: -(r[i_t] or 0.0))
        top_ops = [{
            "ms": (r[i_t] or 0.0) / 1e3,
            "category": str(r[i_cat]),
            "occurrences": r[i_occ] if i_occ is not None else None,
            "bound_by": str(r[i_bound]) if i_bound is not None else "",
            "op": str(r[i_expr])[:120],
        } for r in rows[:top]]
        return by_cat, top_ops
    except Exception as e:
        from ..analysis.jaxpr_lint import WARNING
        _degrade(
            f"XPlane trace in {sessions[-1]!r} unparseable: "
            f"{type(e).__name__}: {e}", severity=WARNING,
            diagnostics=diagnostics)
        return None


def device_total_ms(log_dir: str) -> Optional[float]:
    """Total device self-time (ms) of the newest trace under ``log_dir``
    — the basis of every device timing (bench steps, kernel autotune).

    On a TPU a trace that cannot be read, or that shows no device time,
    raises with the parser's reasons: a caller must never drop to the
    host clock unannounced. Off the chip there is no device plane to
    read and the answer is None."""
    import jax
    diags: list = []
    stats = device_statistics(log_dir, top=1, diagnostics=diags)
    total = sum(stats[0].values()) if stats else 0.0
    if total > 0:
        return total
    if jax.default_backend() == "tpu":
        why = "; ".join(d.message for d in diags) or \
            "the trace holds no device op time"
        raise RuntimeError(
            f"device trace under {log_dir!r} could not be read on a "
            f"TPU: {why}")
    return None


def _fmt_time(ns: float, unit: str) -> str:
    div = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[unit]
    return f"{ns / div:.3f}"


def summary_report(step_times: Sequence[float], log_dir: str,
                   sorted_by=None, op_detail: bool = True,
                   time_unit: str = "ms", top: int = 15) -> str:
    """The full text report (ref profiler_statistic._build_table views):
    Overview (step timing) + OperatorView (host events) + KernelView
    (device HLO categories + top ops)."""
    lines: List[str] = []
    bar = "-" * 78

    lines.append(bar)
    lines.append("Overview")
    lines.append(bar)
    if step_times:
        import statistics
        avg = statistics.mean(step_times)
        lines.append(f"steps: {len(step_times)}   avg: {avg * 1e3:.2f} ms   "
                     f"min: {min(step_times) * 1e3:.2f} ms   "
                     f"max: {max(step_times) * 1e3:.2f} ms   "
                     f"({1.0 / avg:.2f} steps/s)")
    else:
        lines.append("no steps recorded (call Profiler.step() per batch)")

    host = host_statistics()
    if host and op_detail:
        total = sum(s.total_ns for s in host) or 1
        lines.append(bar)
        lines.append(f"OperatorView (host RecordEvent spans, {time_unit})")
        lines.append(bar)
        lines.append(f"{'name':<36}{'calls':>7}{'total':>12}{'avg':>10}"
                     f"{'max':>10}{'ratio':>8}")
        for s in host[:top]:
            lines.append(
                f"{s.name[:35]:<36}{s.calls:>7}"
                f"{_fmt_time(s.total_ns, time_unit):>12}"
                f"{_fmt_time(s.avg_ns, time_unit):>10}"
                f"{_fmt_time(s.max_ns, time_unit):>10}"
                f"{100.0 * s.total_ns / total:>7.1f}%")

    dev = device_statistics(log_dir, top=top)
    if dev is not None:
        by_cat, top_ops = dev
        total_ms = sum(by_cat.values()) or 1.0
        lines.append(bar)
        lines.append("KernelView (device HLO self-time by category)")
        lines.append(bar)
        for cat, ms in sorted(by_cat.items(), key=lambda kv: -kv[1]):
            lines.append(f"{cat:<40}{ms:>10.2f} ms {100 * ms / total_ms:>6.1f}%")
        if op_detail and top_ops:
            lines.append(bar)
            lines.append("Top device ops")
            lines.append(bar)
            for o in top_ops:
                lines.append(f"{o['ms']:>8.2f} ms  {o['category']:<22} "
                             f"{o['op'][:90]}")
    return "\n".join(lines)
