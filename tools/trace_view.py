#!/usr/bin/env python
"""Aggregate a telemetry JSONL dump into the per-phase table + anomalies.

Input is the JSONL written by ``StepTimeline.export_jsonl`` (one record
per step: ``{"kind": "step", "step": N, "phases": {...}, "total_ms": ..,
"hbm_peak_gb": ..}``), optionally interleaved with ``trace.export_jsonl``
span records (``{"kind": "span", "name": .., "t0_ns": .., "dur_ns": ..,
"id": .., "parent": ..}``; files from before ISSUE 26 carry ``dur_us`` and no
ids). Spans with ids also get a table
of SELF time by span name: each span's duration less what its children
cover, which is where a serving step's or a train step's host time actually
goes. The tool reads files and imports nothing of the package.

    python tools/trace_view.py spans.jsonl               # a trace.export_jsonl dump
    python tools/trace_view.py run.jsonl --json          # machine output
    python tools/trace_view.py run.jsonl --factor 2.5    # anomaly knob

Anomaly rule: a step whose ``total_ms`` exceeds ``factor`` (default 3x)
times the rolling median of the preceding ``window`` steps is flagged —
usable on any recorded run without knowing the model.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from typing import Any, Dict, List, Tuple

# Steps of history required before the rolling median is trusted; earlier
# steps (incl. the compile-heavy first ones) are never flagged.
MIN_HISTORY = 5


def load_jsonl(path: str) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(step_records, span_records) from one JSONL file; unknown or broken
    lines are skipped (a truncated tail must not kill the report)."""
    steps, spans = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            kind = rec.get("kind")
            if kind == "step" or ("phases" in rec and "step" in rec):
                steps.append(rec)
            elif kind == "span":
                spans.append(rec)
    steps.sort(key=lambda r: r.get("step", 0))
    return steps, spans


def _dur_ms(sp: Dict[str, Any]) -> float:
    if "dur_ns" in sp:
        return float(sp["dur_ns"]) / 1e6
    return float(sp.get("dur_us", 0.0)) / 1e3


def self_times(spans: List[Dict[str, Any]]) -> Dict[int, int]:
    """``{id: self_ns}``: a span's duration less the part of it that its
    children cover (overlapping children counted once); the arithmetic of
    ``observability.trace.self_times``, kept here so the tool stands alone."""
    kids: Dict[int, List[Tuple[int, int]]] = {}
    for sp in spans:
        if sp.get("parent") is not None:
            kids.setdefault(sp["parent"], []).append(
                (sp["t0_ns"], sp["t0_ns"] + sp["dur_ns"]))
    own: Dict[int, int] = {}
    for sp in spans:
        cur, hi = sp["t0_ns"], sp["t0_ns"] + sp["dur_ns"]
        covered = 0
        for a, b in sorted(kids.get(sp["id"], ())):
            a, b = max(a, cur), min(b, hi)
            if b > a:
                covered += b - a
                cur = b
        own[sp["id"]] = sp["dur_ns"] - covered
    return own


def self_time_table(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Self time by span name, from ``id``/``parent``, sorted by it."""
    linked = [sp for sp in spans if "id" in sp and "t0_ns" in sp
              and not sp.get("incomplete")]
    if not linked:
        return []
    own = self_times(linked)
    agg: Dict[str, Dict[str, float]] = {}
    for sp in linked:
        row = agg.setdefault(sp["name"], {"calls": 0, "self_ms": 0.0,
                                          "total_ms": 0.0})
        row["calls"] += 1
        row["self_ms"] += own[sp["id"]] / 1e6
        row["total_ms"] += sp["dur_ns"] / 1e6
    return [{"span": name, "calls": r["calls"],
             "self_ms": round(r["self_ms"], 3),
             "total_ms": round(r["total_ms"], 3),
             "avg_self_ms": round(r["self_ms"] / r["calls"], 4)}
            for name, r in sorted(agg.items(),
                                  key=lambda kv: -kv[1]["self_ms"])]


def phase_table(steps: List[Dict[str, Any]],
                spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-phase aggregate rows sorted by total time descending."""
    agg: Dict[str, Dict[str, float]] = {}

    def add(name: str, ms: float):
        row = agg.setdefault(name, {"calls": 0, "total_ms": 0.0,
                                    "max_ms": 0.0})
        row["calls"] += 1
        row["total_ms"] += ms
        row["max_ms"] = max(row["max_ms"], ms)

    for s in steps:
        for name, ms in (s.get("phases") or {}).items():
            add(name, float(ms))
    for sp in spans:
        # span names are "step/<phase>" (step_monitor) or free-form
        name = sp.get("name", "")
        if name.startswith("step/"):
            continue  # already counted via the step record's phases
        if name:
            add(f"span:{name}", _dur_ms(sp))

    total = sum(r["total_ms"] for r in agg.values()) or 1.0
    rows = []
    for name in sorted(agg, key=lambda n: -agg[n]["total_ms"]):
        r = agg[name]
        rows.append({
            "phase": name,
            "calls": r["calls"],
            "total_ms": round(r["total_ms"], 3),
            "avg_ms": round(r["total_ms"] / max(r["calls"], 1), 3),
            "max_ms": round(r["max_ms"], 3),
            "share_pct": round(100.0 * r["total_ms"] / total, 1),
        })
    return rows


def find_anomalies(steps: List[Dict[str, Any]], factor: float = 3.0,
                   window: int = 32) -> List[Dict[str, Any]]:
    """Steps slower than ``factor`` x the rolling median of the preceding
    ``window`` steps' total_ms."""
    out = []
    history: List[float] = []
    for s in steps:
        t = s.get("total_ms")
        if t is None:
            continue
        if len(history) >= MIN_HISTORY:
            med = statistics.median(history[-window:])
            if med > 0 and t > factor * med:
                out.append({"step": s.get("step"),
                            "total_ms": round(float(t), 3),
                            "rolling_median_ms": round(med, 3),
                            "slowdown_x": round(float(t) / med, 2),
                            "phases": s.get("phases", {})})
        history.append(float(t))
    return out


def comm_summary(steps: List[Dict[str, Any]],
                 spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate the communication-overlap tier's signal: the ``comm``
    step phase (dispatch-level bucketed reductions) plus ``comm/*`` spans
    (decomposed collective-matmul call sites — their attrs carry the
    static hop plan: hop count, bytes per hop, axis size)."""
    phase_ms = 0.0
    phase_calls = 0
    for s in steps:
        ms = (s.get("phases") or {}).get("comm")
        if ms is not None:
            phase_ms += float(ms)
            phase_calls += 1
    ops: Dict[str, Dict[str, float]] = {}
    for sp in spans:
        name = sp.get("name", "")
        if not name.startswith("comm/"):
            continue
        attrs = sp.get("attrs") or {}
        row = ops.setdefault(name[len("comm/"):],
                             {"calls": 0, "total_ms": 0.0, "hops": 0,
                              "bytes_moved": 0})
        row["calls"] += 1
        row["total_ms"] += _dur_ms(sp)
        hops = int(attrs.get("hops", 0))
        row["hops"] += hops
        row["bytes_moved"] += hops * int(attrs.get("bytes_per_hop", 0))
    for row in ops.values():
        row["total_ms"] = round(row["total_ms"], 3)
    return {
        "phase_total_ms": round(phase_ms, 3),
        "phase_steps": phase_calls,
        "decomposed_ops": ops,
    }


def summarize(steps: List[Dict[str, Any]], spans: List[Dict[str, Any]],
              factor: float = 3.0, window: int = 32) -> Dict[str, Any]:
    totals = [float(s["total_ms"]) for s in steps if "total_ms" in s]
    hbm = [s.get("hbm_peak_gb") for s in steps
           if s.get("hbm_peak_gb") is not None]
    return {
        "steps": len(steps),
        "spans": len(spans),
        "avg_step_ms": round(sum(totals) / len(totals), 3) if totals else None,
        "median_step_ms": round(statistics.median(totals), 3)
        if totals else None,
        "max_step_ms": round(max(totals), 3) if totals else None,
        "hbm_peak_gb": max(hbm) if hbm else None,
        "phases": phase_table(steps, spans),
        "self_time": self_time_table(spans),
        "comm": comm_summary(steps, spans),
        "anomalies": find_anomalies(steps, factor=factor, window=window),
    }


def render_text(summary: Dict[str, Any]) -> str:
    bar = "-" * 72
    lines = [bar, "Telemetry timeline", bar]
    lines.append(
        f"steps: {summary['steps']}   avg: {summary['avg_step_ms']} ms   "
        f"median: {summary['median_step_ms']} ms   "
        f"max: {summary['max_step_ms']} ms" +
        (f"   hbm peak: {summary['hbm_peak_gb']} GB"
         if summary["hbm_peak_gb"] is not None else ""))
    lines.append(bar)
    lines.append(f"{'phase':<24}{'calls':>7}{'total ms':>12}{'avg ms':>10}"
                 f"{'max ms':>10}{'share':>8}")
    for r in summary["phases"]:
        lines.append(f"{r['phase'][:23]:<24}{r['calls']:>7}"
                     f"{r['total_ms']:>12.3f}{r['avg_ms']:>10.3f}"
                     f"{r['max_ms']:>10.3f}{r['share_pct']:>7.1f}%")
    if summary.get("self_time"):
        lines.append(bar)
        lines.append(f"{'span (self time)':<32}{'calls':>7}{'self ms':>12}"
                     f"{'avg self':>10}{'total ms':>12}")
        for r in summary["self_time"]:
            lines.append(f"{r['span'][:31]:<32}{r['calls']:>7}"
                         f"{r['self_ms']:>12.3f}{r['avg_self_ms']:>10.4f}"
                         f"{r['total_ms']:>12.3f}")
    comm = summary.get("comm") or {}
    if comm.get("phase_total_ms") or comm.get("decomposed_ops"):
        lines.append(bar)
        lines.append(
            f"comm overlap: {comm['phase_total_ms']} ms dispatch-level "
            f"across {comm['phase_steps']} step(s)")
        for op, row in sorted(comm["decomposed_ops"].items()):
            lines.append(
                f"  {op}: {row['calls']} call(s), {row['hops']} hops, "
                f"{row['bytes_moved'] / 2**20:.2f} MiB moved, "
                f"{row['total_ms']} ms")
    anomalies = summary["anomalies"]
    lines.append(bar)
    if anomalies:
        lines.append(f"{len(anomalies)} anomalous step(s) "
                     "(> factor x rolling median):")
        for a in anomalies:
            lines.append(
                f"  step {a['step']}: {a['total_ms']} ms "
                f"({a['slowdown_x']}x the rolling median "
                f"{a['rolling_median_ms']} ms)")
    else:
        lines.append("no step-time anomalies")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("path", help="telemetry JSONL file")
    p.add_argument("--json", action="store_true",
                   help="emit the machine-readable summary")
    p.add_argument("--factor", type=float, default=3.0,
                   help="anomaly threshold vs rolling median (default 3.0)")
    p.add_argument("--window", type=int, default=32,
                   help="rolling-median window in steps (default 32)")
    p.add_argument("--fail-on-anomaly", action="store_true",
                   help="exit nonzero when any step is anomalous (CI gate)")
    a = p.parse_args(argv)
    steps, spans = load_jsonl(a.path)
    summary = summarize(steps, spans, factor=a.factor, window=a.window)
    if a.json:
        print(json.dumps(summary, indent=2))
    else:
        print(render_text(summary))
    if a.fail_on_anomaly and summary["anomalies"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
