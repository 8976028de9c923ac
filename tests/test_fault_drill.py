"""End-to-end fault drill (ISSUE 7 acceptance): the quick tier-1-safe drill
— train a tiny GPT under the elastic manager, SIGKILL it mid-step AND
mid-checkpoint-write, relaunch, resume from latest_complete() — must finish
with BITWISE loss parity vs an uninterrupted run and emit the measured
goodput record. Runs ``tools/fault_drill.py --quick`` as a subprocess, the
same entry CI uses."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quick_drill_subprocess(tmp_path):
    out = str(tmp_path / "report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fault_drill.py"),
         "--quick", "--workdir", str(tmp_path / "drill"), "--out", out],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out) as f:
        report = json.load(f)

    # the drill finished and recovered exactly
    assert report["rc"] == 0
    assert report["done"] is True
    parity = report["parity"]
    assert parity["bitwise_equal"] is True, parity
    assert parity["missing_steps"] == []

    # both planned fault kinds actually fired (mid-step + mid-ckpt-write)
    fired_kinds = {e.split("@")[0] for e in report["fired_events"]}
    assert fired_kinds == {"mid_step", "mid_ckpt_write"}

    # the measured goodput record
    g = report["goodput_record"]
    assert 0.0 < g["goodput"] <= 1.0
    assert g["restarts"] == 2            # one relaunch per kill
    assert g["wall_s"] > g["useful_step_s"] > 0.0
    assert g["steps_committed"] == report["config"]["total_steps"]
    assert g["lost_steps"] >= 1          # a SIGKILL always loses work
    assert g["ckpt_save"]["count"] >= 1
    assert g["ckpt_restore"]["count"] == 2

    # flight-recorder postmortem (ISSUE 15): the run's story is
    # reconstructed from the black boxes + journals alone and must match
    # the injected plan — kinds, steps, and who-died-first ordering
    pm = report["postmortem"]
    assert pm["ok"], pm
    assert pm["coherent"], pm["coherence"]
    assert pm["recorder_files"] == 3     # one per incarnation (2 kills)
    assert pm["plan_check"]["matches"]
    assert pm["plan_check"]["kill_order_ok"] is True
    planned = {(e["kind"], e["step"]) for e in report["plan"]["events"]}
    assert {(d["kind"], d["step"]) for d in pm["deaths"]} == planned
    total = report["config"]["total_steps"]
    assert pm["last_committed_steps"] == {"trainer.r0": total - 1}
    assert g["ckpt_save"]["mean_ms"] > 0.0


def test_drill_resume_used_checkpoints(tmp_path):
    """White-box follow-up on the same machinery, in-process where cheap:
    a torn snapshot left by the mid-ckpt-write kill must exist as a
    ``.tmp.*`` dir (never a committed ``step_*``) — run the drill pieces'
    invariants without subprocesses."""
    from paddle_tpu.fault import CheckpointManager, FaultPlan
    from paddle_tpu.fault.drill import quick_config

    cfg = quick_config()
    plan = FaultPlan.from_seed(cfg["seed"], cfg["total_steps"],
                               n_kills=cfg["n_kills"], kinds=cfg["kinds"])
    kinds = [e.kind for e in plan.events]
    assert "mid_step" in kinds and "mid_ckpt_write" in kinds
    # quick plan is stable under the pinned seed — CI drills are replayable
    plan2 = FaultPlan.from_seed(cfg["seed"], cfg["total_steps"],
                                n_kills=cfg["n_kills"], kinds=cfg["kinds"])
    assert plan.to_json() == plan2.to_json()

    cm = CheckpointManager(str(tmp_path / "ckpt"))
    import numpy as np
    cm.save(2, {"x": np.ones((2,))}, block=True)
    os.makedirs(os.path.join(cm.directory, ".tmp.step_4"))
    open(os.path.join(cm.directory, ".tmp.step_4", "arr_00000.npy"),
         "wb").close()
    assert cm.latest_complete() == 2
