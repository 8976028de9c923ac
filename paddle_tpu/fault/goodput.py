"""Goodput accounting: make failure recovery a measured number.

``goodput = useful_step_time / wall_time_including_restart`` — the
fraction of the run's wall clock (process startup, compiles, relaunches,
checkpoint restores, re-executed steps included) that went into step
compute the run actually kept. A preemption costs goodput three ways:
the work since the last checkpoint is re-executed (lost steps), the
relaunch pays startup + restore, and the torn checkpoint (if the death
hit mid-write) pushes the resume point one snapshot further back. The
drill (``tools/fault_drill.py``) reports all three components alongside
the ratio so regressions are attributable.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

__all__ = ["compute_goodput", "parse_train_log"]


def parse_train_log(lines: Iterable[str]) -> Dict[str, Any]:
    """Split a drill trainer's JSONL log into per-step records and events.

    Returns ``steps`` (step -> final {"loss", "t"} — re-executed steps keep
    the LAST occurrence), ``executions`` (total step-lines, counting
    re-runs), ``events`` (ordered event records: start/resumed/ckpt_saved/
    ckpt_restored/anomaly/rewind/skip_batch/done), ``lost_steps``
    (step-lines that a later incarnation re-executed — committed work
    thrown away by a fault), and the training-health aggregates:
    ``skipped_batches`` (poisoned positions dropped),
    ``rewound_steps`` (steps re-executed because the guardian rewound to
    last-good — a subset of ``lost_steps``' causes), and
    ``detection_latency_steps`` (per-anomaly ``detected - injected``
    step counts, where the log carries both)."""
    import json
    steps: Dict[int, Dict[str, Any]] = {}
    events: List[Dict[str, Any]] = []
    executions = 0
    lost = 0
    skipped = 0
    rewound = 0
    latencies: List[int] = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        if "step" in rec and "loss" in rec:
            executions += 1
            s = int(rec["step"])
            if s in steps:
                lost += 1  # the earlier execution was thrown away
            steps[s] = rec
        elif "event" in rec:
            events.append(rec)
            kind = rec["event"]
            if kind == "skip_batch":
                skipped += 1
            elif kind == "rewind":
                rewound += max(0, int(rec.get("from", 0))
                               - int(rec.get("to", 0)))
            elif kind == "anomaly" and \
                    rec.get("latency_steps") is not None:
                latencies.append(int(rec["latency_steps"]))
    return {"steps": steps, "events": events, "executions": executions,
            "lost_steps": lost, "skipped_batches": skipped,
            "rewound_steps": rewound,
            "detection_latency_steps": latencies}


def compute_goodput(log: Dict[str, Any], wall_s: float,
                    restarts: Optional[int] = None) -> Dict[str, Any]:
    """Aggregate one fault-injected run's log into the goodput record of
    the drill's report. ``log`` is :func:`parse_train_log` output; if
    ``restarts`` is None it is inferred from the ``start`` events (every
    incarnation logs one)."""
    steps = log["steps"]
    events = log["events"]
    useful_s = sum(float(r.get("t", 0.0)) for r in steps.values())
    if restarts is None:
        restarts = max(0, sum(1 for e in events
                              if e.get("event") == "start") - 1)
    save_ms = [float(e["ms"]) for e in events
               if e.get("event") == "ckpt_saved"]
    restore_ms = [float(e["ms"]) for e in events
                  if e.get("event") == "ckpt_restored"]

    def stats(xs):
        if not xs:
            return {"count": 0}
        return {"count": len(xs),
                "mean_ms": round(sum(xs) / len(xs), 2),
                "max_ms": round(max(xs), 2)}

    goodput = (useful_s / wall_s) if wall_s > 0 else 0.0
    latencies = list(log.get("detection_latency_steps", ()))
    record = {
        "goodput": round(goodput, 4),
        "useful_step_s": round(useful_s, 4),
        "wall_s": round(wall_s, 4),
        "restarts": int(restarts),
        "lost_steps": int(log["lost_steps"]),
        "steps_committed": len(steps),
        "step_executions": int(log["executions"]),
        "ckpt_save": stats(save_ms),
        "ckpt_restore": stats(restore_ms),
        # training-health aggregates (zero on a crash-only drill)
        "skipped_batches": int(log.get("skipped_batches", 0)),
        "rewound_steps": int(log.get("rewound_steps", 0)),
        "detection_latency_steps": {
            "count": len(latencies),
            "max": max(latencies) if latencies else 0,
            "mean": (round(sum(latencies) / len(latencies), 3)
                     if latencies else 0.0),
        },
    }
    _publish(record)
    return record


def _publish(record: Dict[str, Any]) -> None:
    """Mirror the drill-level aggregates into the shared metrics registry
    so Prometheus/JSON exposition carries ``fault.*`` series."""
    from ..observability import metrics
    metrics.gauge("fault.goodput",
                  "useful step time / wall time incl. restarts"
                  ).labels().set(record["goodput"])
    metrics.gauge("fault.lost_steps",
                  "steps re-executed after faults").labels().set(
                      record["lost_steps"])
    metrics.gauge("fault.restarts",
                  "relaunches observed by the drill").labels().set(
                      record["restarts"])
    metrics.gauge("fault.skipped_batches",
                  "poisoned batch positions the guardian dropped"
                  ).labels().set(record["skipped_batches"])
    metrics.gauge("fault.rewound_steps",
                  "steps re-executed by rewind-to-last-good recoveries"
                  ).labels().set(record["rewound_steps"])
    metrics.gauge("fault.detection_latency_steps",
                  "max anomaly detection latency in steps"
                  ).labels().set(record["detection_latency_steps"]["max"])
