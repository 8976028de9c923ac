"""The end-to-end fault drill: train → kill → relaunch → resume → measure.

Runs the drill trainer (``fault/_trainer.py``) as a subprocess pod under
``ElasticManager`` (the same watch/relaunch loop a real deployment uses),
with a deterministic :class:`~paddle_tpu.fault.injection.FaultPlan` killing
it mid-step, mid-checkpoint-write, or via SIGTERM; then replays the same
number of steps uninterrupted and checks **bitwise** loss parity — the
proof that checkpoint + PRNG + batch-cursor state capture is complete.
The run's goodput record (useful step time / wall time including
restarts, restart count, lost steps, checkpoint save/restore durations)
is part of the drill's report.

CLI: ``tools/fault_drill.py`` (``--quick`` is the tier-1-safe mode the
test suite runs as a subprocess).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional, Sequence

from . import _trainer, goodput
from .injection import FaultPlan

__all__ = ["quick_config", "run_drill"]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAINER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_trainer.py")


def quick_config() -> Dict[str, Any]:
    """The tier-1-safe drill: tiny model, 2 kills (one mid-step, one
    mid-checkpoint-write), well under a minute on a laptop CPU."""
    return dict(total_steps=8, ckpt_every=2, seed=7, n_kills=2,
                kinds=("mid_step", "mid_ckpt_write"), size="quick")


def quick_health_config() -> Dict[str, Any]:
    """``--quick --health``: the 2-kill drill chained with one
    ``inject_nan`` and one ``inject_hang`` event — four faults, the same
    bitwise parity gate, still well under 90 s."""
    return dict(total_steps=12, ckpt_every=3, seed=7, n_kills=4,
                kinds=("mid_step", "mid_ckpt_write", "inject_nan",
                       "inject_hang"),
                size="quick", health=True)


def _fault_env(workdir: str, total_steps: int, ckpt_every: int,
               plan: FaultPlan, size: str) -> Dict[str, str]:
    env = dict(os.environ)
    env.update({
        "FAULT_WORK_DIR": workdir,
        "FAULT_TOTAL_STEPS": str(total_steps),
        "FAULT_CKPT_EVERY": str(ckpt_every),
        "FAULT_PLAN": plan.to_json(),
        "FAULT_SIZE": size,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    return env


def _dodge_resume_boundaries(plan: FaultPlan, ckpt_every: int,
                             total_steps: int) -> FaultPlan:
    """Give every ``inject_hang`` event >= 2 steps of runway after any
    checkpoint-resume boundary (step 0 and multiples of ``ckpt_every``):
    an incarnation's first dispatch is the XLA compile (watchdog unarmed,
    unrecorded) and its second seeds the step-time median — a hang
    landing earlier would stall undetected. Deterministic (pure
    arithmetic on the seeded plan). Requires ``ckpt_every >= 3`` so such
    steps exist."""
    from .injection import FaultEvent
    if not any(e.kind == "inject_hang" for e in plan.events):
        return plan
    if ckpt_every < 3:
        raise ValueError(
            "health drills with inject_hang need ckpt_every >= 3: the "
            "watchdog arms two steps after each resume boundary, and "
            f"with ckpt_every={ckpt_every} no step is that far from one")
    taken = {e.step for e in plan.events}
    moved = []
    for e in plan.events:
        s = e.step
        if e.kind == "inject_hang":
            taken.discard(e.step)
            cands = [x for x in range(2, total_steps - 1)
                     if x % ckpt_every >= 2 and x not in taken]
            if not cands:
                raise ValueError(
                    f"no watchdog-armable step for inject_hang in "
                    f"[2, {total_steps - 2}] with ckpt_every={ckpt_every}")
            s = min(cands, key=lambda x: (abs(x - e.step), x))
            taken.add(s)
        moved.append(FaultEvent(e.kind, s))
    return FaultPlan(moved, seed=plan.seed)


def run_drill(workdir: str, total_steps: int = 8, ckpt_every: int = 2,
              seed: int = 7, n_kills: int = 2,
              kinds: Sequence[str] = ("mid_step", "mid_ckpt_write"),
              size: str = "quick", max_restarts: Optional[int] = None,
              reference: str = "inline",
              health: bool = False, canary_every: int = 3,
              flight_recorder: bool = True,
              fleet_telemetry: bool = True
              ) -> Dict[str, Any]:
    """Run the fault-injected job + the uninterrupted reference, return the
    full report (goodput record, parity verdict, plan, per-run logs).

    ``reference`` is ``"inline"`` (run the reference trainer in this
    process — the step builder pins a single-device mesh, so the
    trajectory is identical to the subprocess run) or ``"subprocess"``.

    ``health=True`` arms the guarded trainer (sentinel + watchdog +
    canary + Guardian) in BOTH runs; the reference is handed the batch
    positions the fault run's recovery policies will poison (derived
    statically from the plan — ``inject_nan``/``inject_loss_spike``
    events skip their batch), so parity compares against "the clean run
    that never saw that batch".
    """
    from ..distributed.launch import LaunchConfig, launch

    plan = FaultPlan.from_seed(seed, total_steps, n_kills=n_kills,
                               kinds=tuple(kinds), min_step=1)
    if health:
        plan = _dodge_resume_boundaries(plan, ckpt_every, total_steps)
    # batch positions the poison-kind events will skip: with one poisoned
    # event the stream position IS the step (later events shift by the
    # number of earlier skips — mirror the cursor arithmetic)
    poison_steps = sorted(e.step for e in plan.events
                          if e.kind in ("inject_nan", "inject_loss_spike"))
    skips = [s + i for i, s in enumerate(poison_steps)]
    if max_restarts is None:
        max_restarts = n_kills + 2  # headroom over the planned faults
    fault_dir = os.path.join(workdir, "fault")
    ref_dir = os.path.join(workdir, "reference")
    os.makedirs(fault_dir, exist_ok=True)
    os.makedirs(ref_dir, exist_ok=True)

    env = _fault_env(fault_dir, total_steps, ckpt_every, plan, size)
    if flight_recorder:
        # every incarnation writes a crash-persistent black box; the
        # postmortem below reconstructs the run from those + journals
        env["FLAGS_flight_recorder"] = "on"
    if fleet_telemetry:
        # the live plane: every incarnation exports registry snapshots
        # under fault_dir/fleet while it runs — the drill-end view must
        # show the killed incarnations as silent and the survivor exited
        env["FLAGS_fleet_telemetry"] = "on"
        env["FLAGS_fleet_export_interval"] = "0.2"
    if health:
        env.update({"FAULT_HEALTH": "1",
                    "FAULT_CANARY_EVERY": str(canary_every),
                    # the stall comfortably outlives any plausible
                    # deadline — the watchdog kills the process at the
                    # deadline, so a longer sleep costs no wall time
                    "FAULT_HANG_SLEEP_S": "8.0"})
    cfg = LaunchConfig(
        nproc_per_node=1, log_dir=os.path.join(fault_dir, "logs"),
        envs=env)
    t0 = time.perf_counter()
    rc = launch(cfg, TRAINER, max_restarts=max_restarts,
                elastic_dir=os.path.join(fault_dir, "hb"))
    wall_s = time.perf_counter() - t0

    report: Dict[str, Any] = {
        "rc": rc, "plan": json.loads(plan.to_json()),
        "config": {"total_steps": total_steps, "ckpt_every": ckpt_every,
                   "seed": seed, "size": size,
                   "max_restarts": max_restarts, "health": health,
                   "skips": skips},
    }
    log_path = os.path.join(fault_dir, "train_log.jsonl")
    if rc != 0 or not os.path.exists(log_path):
        report["error"] = f"fault run exited rc={rc}"
        return report
    with open(log_path) as f:
        flog = goodput.parse_train_log(f)
    report["goodput_record"] = goodput.compute_goodput(flog, wall_s)
    report["fired_events"] = sorted(
        _read_fired(os.path.join(fault_dir, "fired.json")))
    report["done"] = any(e.get("event") == "done" for e in flog["events"])
    if health:
        report["health"] = {
            "anomalies": [e for e in flog["events"]
                          if e.get("event") == "anomaly"],
            "skipped_batches": flog["skipped_batches"],
            "rewound_steps": flog["rewound_steps"],
            "detection_latency_steps": flog["detection_latency_steps"],
        }

    # -- the uninterrupted reference + bitwise parity -----------------------
    if reference == "inline":
        _trainer.train(ref_dir, total_steps=total_steps,
                       ckpt_every=ckpt_every, plan_json="", size=size,
                       health=health, skips=tuple(skips),
                       canary_every=(canary_every if health else 0))
        ref_rc = 0
    else:
        env_ref = _fault_env(ref_dir, total_steps, ckpt_every,
                             FaultPlan([]), size)
        if health:
            env_ref.update({
                "FAULT_HEALTH": "1",
                "FAULT_CANARY_EVERY": str(canary_every),
                "FAULT_SKIPS": ",".join(str(s) for s in skips)})
        cfg_ref = LaunchConfig(
            nproc_per_node=1, log_dir=os.path.join(ref_dir, "logs"),
            envs=env_ref)
        ref_rc = launch(cfg_ref, TRAINER)
    with open(os.path.join(ref_dir, "train_log.jsonl")) as f:
        rlog = goodput.parse_train_log(f)
    report["parity"] = _parity(flog, rlog, total_steps)
    report["reference_rc"] = ref_rc

    # -- postmortem: the drill doubles as the flight recorder's proof —
    # the reconstruction from recorder files + journals alone must match
    # the injected plan (kinds, steps, kill ordering) and cohere with
    # the train log
    if flight_recorder:
        from ..observability import fleet
        report["postmortem"] = fleet.postmortem_report(
            fault_dir, plan=report["plan"]["events"],
            ckpt_every=ckpt_every)

    # -- live fleet plane: the trainer exported snapshots the whole run —
    # the final incarnation must have said its closed farewell and every
    # SIGKILLed one must be a silent incarnation in the aggregated view
    if fleet_telemetry:
        from ..observability import live as fleet_live
        view = fleet_live.aggregate(fault_dir)
        worker = next(iter(view["workers"].values()), {})
        report["fleet"] = {
            "workers": {k: w["status"]
                        for k, w in view["workers"].items()},
            "incarnations_seen": int(worker.get("incarnations", 0)),
            "silent_incarnations": list(
                worker.get("silent_incarnations", [])),
            "final_status": worker.get("status"),
            "final_step": worker.get("step"),
            "derived": view["derived"],
            "ok": bool(worker) and worker.get("status") == "exited",
        }
    return report


def _read_fired(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return []


def _parity(flog, rlog, total_steps: int) -> Dict[str, Any]:
    """Bitwise comparison of the final loss per step. float(loss) is an
    exact float32→float64 widening and json round-trips doubles exactly,
    so ``==`` here IS bitwise equality of the computed losses."""
    fsteps = {s: r["loss"] for s, r in flog["steps"].items()}
    rsteps = {s: r["loss"] for s, r in rlog["steps"].items()}
    missing = [s for s in range(total_steps)
               if s not in fsteps or s not in rsteps]
    diffs = [{"step": s, "fault": fsteps[s], "reference": rsteps[s]}
             for s in range(total_steps)
             if s in fsteps and s in rsteps and fsteps[s] != rsteps[s]]
    return {"bitwise_equal": not missing and not diffs,
            "steps": total_steps, "missing_steps": missing,
            "mismatches": diffs[:8]}


def report_summary(report: Dict[str, Any]) -> str:
    g = report.get("goodput_record", {})
    p = report.get("parity", {})
    lines = [
        f"fault drill rc={report.get('rc')} "
        f"done={report.get('done')}",
        f"  plan: {[e['kind'] + '@' + str(e['step']) for e in report['plan']['events']]}",
        f"  fired: {report.get('fired_events')}",
        f"  goodput={g.get('goodput')} "
        f"(useful {g.get('useful_step_s')}s / wall {g.get('wall_s')}s), "
        f"restarts={g.get('restarts')}, lost_steps={g.get('lost_steps')}",
        f"  ckpt saves={g.get('ckpt_save', {}).get('count')} "
        f"(mean {g.get('ckpt_save', {}).get('mean_ms')} ms), "
        f"restores={g.get('ckpt_restore', {}).get('count')} "
        f"(mean {g.get('ckpt_restore', {}).get('mean_ms')} ms)",
        f"  parity: bitwise_equal={p.get('bitwise_equal')} "
        f"over {p.get('steps')} steps",
    ]
    pm = report.get("postmortem")
    if pm:
        pc = pm.get("plan_check") or {}
        lines.append(
            f"  postmortem: ok={pm.get('ok')} "
            f"coherent={pm.get('coherent')} "
            f"recorder_files={pm.get('recorder_files')} "
            f"last_steps={pm.get('last_committed_steps')} "
            f"deaths={[(d['kind'], d['step']) for d in pm.get('deaths', [])]} "
            f"kill_order_ok={pc.get('kill_order_ok')}")
    h = report.get("health")
    if h:
        lines.append(
            f"  health: anomalies="
            f"{[a.get('kind') for a in h.get('anomalies', [])]} "
            f"latency_steps={h.get('detection_latency_steps')} "
            f"skipped={h.get('skipped_batches')} "
            f"rewound={h.get('rewound_steps')}")
    fl = report.get("fleet")
    if fl:
        lines.append(
            f"  fleet: final={fl.get('final_status')} "
            f"step={fl.get('final_step')} "
            f"silent_incs={fl.get('silent_incarnations')} "
            f"ok={fl.get('ok')}")
    return "\n".join(lines)
