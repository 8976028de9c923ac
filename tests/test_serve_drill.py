"""End-to-end serving fault drill (ISSUE 9 acceptance): the quick
tier-1-safe drill — serve a deterministic trace under the elastic
launcher, SIGKILL the worker mid-decode AND mid-spill, relaunch, replay
the submitted-but-unacknowledged requests from the fsynced journal — must
end with zero lost requests, zero duplicated requests, and token-exact
outputs vs ``model.generate`` for every survivor. Runs
``tools/serve_drill.py --quick`` as a subprocess, the same entry CI uses
(mirroring ``test_fault_drill.py``)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quick_serve_drill_subprocess(tmp_path):
    out = str(tmp_path / "report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_drill.py"),
         "--quick", "--workdir", str(tmp_path / "drill"), "--out", out],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out) as f:
        report = json.load(f)

    # the worker pod finished and the drill verdict is clean
    assert report["rc"] == 0 and report["ok"] is True

    # both planned kill kinds actually fired (mid-decode + mid-spill),
    # one relaunch per kill
    fired_kinds = {e.split("@")[0] for e in report["fired_events"]}
    assert fired_kinds == {"mid_decode", "mid_spill"}
    assert len(report["fired_events"]) >= 2
    assert report["restarts"] == 2

    # exactly-once: every request acknowledged once, none lost, none
    # duplicated, across all incarnations
    once = report["exactly_once"]
    assert once["exactly_once"] is True
    assert once["lost"] == [] and once["duplicated"] == []
    assert once["expected"] == report["config"]["requests"]
    assert once["launches"] == 3          # initial + one per kill

    # survivors are token-exact vs model.generate
    assert report["token_exact"] is True
    assert report["served"] == report["config"]["requests"]
    assert report["mismatched_rids"] == []

    # flight-recorder postmortem (ISSUE 15): the serving black boxes +
    # journals reconstruct the kills and every served output carries a
    # journaled ack
    pm = report["postmortem"]
    assert pm["ok"], pm
    assert pm["coherent"], pm["coherence"]
    assert pm["recorder_files"] == 3     # one per incarnation (2 kills)
    assert pm["exactly_once"]["exactly_once"] is True
    planned = {(e["kind"], e["step"]) for e in report["plan"]["events"]}
    assert {(d["kind"], d["step"]) for d in pm["deaths"]} == planned


def test_drill_components_inprocess(tmp_path):
    """White-box follow-ups on the drill machinery, cheap and local:
    the quick plan names both serving kill kinds; FaultPlan JSON
    round-trips the serving kinds; the worker's trace loader
    reconstructs deadline/priority fields."""
    import numpy as np
    from paddle_tpu.fault.injection import FaultEvent, FaultPlan
    from paddle_tpu.serving.drill import quick_serve_config
    from paddle_tpu.serving._drill_worker import load_trace

    cfg = quick_serve_config()
    kinds = {k for k, _ in cfg["events"]}
    assert kinds == {"mid_decode", "mid_spill"}

    plan = FaultPlan([FaultEvent(k, s) for k, s in cfg["events"]])
    plan2 = FaultPlan.from_json(plan.to_json())
    assert [e.key for e in plan2.events] == [e.key for e in plan.events]

    path = tmp_path / "trace.jsonl"
    path.write_text(json.dumps(
        {"rid": "a", "prompt": [1, 2, 3], "max_new_tokens": 4,
         "deadline_s": 1.5, "priority": 2}) + "\n")
    [req] = load_trace(str(path))
    assert req.rid == "a" and req.max_new_tokens == 4
    assert req.deadline_s == 1.5 and req.priority == 2
    np.testing.assert_array_equal(req.prompt_ids, [1, 2, 3])


def test_prefix_cache_serve_drill_subprocess(tmp_path):
    """ISSUE 13 satellite: the kill-and-replay drill with the radix
    prefix cache armed and an 8-token shared prompt prefix — the
    relaunch replays re-attach to pages the first replayed sharer
    re-prefills (grouped by the journaled prompt hashes), and
    exactly-once + token-exactness must hold unchanged."""
    out = str(tmp_path / "report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_drill.py"),
         "--quick", "--prefix-cache",
         "--workdir", str(tmp_path / "drill"), "--out", out],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out) as f:
        report = json.load(f)
    assert report["ok"] is True and report["token_exact"] is True
    assert report["config"]["prefix_cache"] == 1
    assert report["config"]["shared_prefix"] == 8
    once = report["exactly_once"]
    assert once["exactly_once"] is True and once["lost"] == []
    # every incarnation journaled prompt hashes for its submissions
    sys.path.insert(0, REPO)
    from paddle_tpu.serving.resilience import RequestJournal, prompt_hash
    j = RequestJournal(str(tmp_path / "drill" / "journal.jsonl"))
    shas = j.prompt_hashes()
    assert len(shas) == report["config"]["requests"]
    # hashes are content hashes: recompute from the trace and compare
    with open(tmp_path / "drill" / "trace.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            assert shas[rec["rid"]] == prompt_hash(rec["prompt"])
