"""The serving fault drill: serve → kill → relaunch → replay → verify.

The training drill (``fault/drill.py``) proves checkpointed training
recovers bitwise; this is the serving counterpart for ISSUE 9 — the
worker (``serving/_drill_worker.py``) serves a deterministic request
trace under the elastic launcher while a :class:`FaultPlan` SIGKILLs it
**mid-decode** (after an iteration's compute, before any token commit)
and **mid-spill** (inside the paged cache's host spill, before the
blocks are freed). Every incarnation replays exactly the
submitted-but-unacknowledged requests out of the fsynced
:class:`~paddle_tpu.serving.resilience.RequestJournal`, and the drill
asserts the serving resilience contract:

- **zero lost requests** — every trace rid acknowledged;
- **zero duplicated requests** — exactly one acknowledgment each;
- **token-exact survivors** — every served output equals
  ``model.generate`` on the same prompt (greedy), kills or not.

CLI: ``tools/serve_drill.py`` (``--quick`` is the tier-1-safe mode
``tests/test_serve_drill.py`` runs as a subprocess).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

from .resilience import RequestJournal

__all__ = ["quick_serve_config", "run_serve_drill", "run_overload_drill",
           "report_summary"]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_drill_worker.py")


def quick_serve_config() -> Dict[str, Any]:
    """The tier-1-safe drill: tiny GPT, a trace that forces preemption
    pressure (so the mid-spill seam is reached), two kills — one
    mid-decode, one mid-spill — well under two minutes on a laptop CPU.

    ``prefix_cache=1`` arms the radix tree in the worker and
    ``shared_prefix=N`` gives every trace prompt an N-token common
    prefix, so the relaunch-replay path exercises tree re-attachment
    (ISSUE 13 satellite: token-exactness must survive kills with the
    prefix cache on)."""
    return dict(
        requests=6, prompt_lo=8, prompt_hi=14, max_new=8, trace_seed=3,
        model_seed=7, vocab=128, hidden=48, layers=2, heads=4, max_pos=32,
        block_size=4, num_blocks=10, max_batch=4,
        prefix_cache=0, shared_prefix=0,
        # (kind, counter): decode iteration 4 and the very first spill —
        # both guaranteed to be reached before anything completes
        events=(("mid_decode", 4), ("mid_spill", 1)))


def _write_trace(path: str, cfg: Dict[str, Any]) -> list:
    import numpy as np
    rng = np.random.default_rng(cfg["trace_seed"])
    shared = rng.integers(0, cfg["vocab"],
                          int(cfg.get("shared_prefix", 0))).tolist()
    trace = []
    for i in range(cfg["requests"]):
        plen = int(rng.integers(cfg["prompt_lo"], cfg["prompt_hi"] + 1))
        prompt = shared + rng.integers(0, cfg["vocab"], plen).tolist()
        trace.append({"rid": f"r{i}", "prompt": prompt,
                      "max_new_tokens": int(cfg["max_new"])})
    with open(path, "w") as f:
        for rec in trace:
            f.write(json.dumps(rec) + "\n")
    return trace


def _reference_outputs(trace, cfg) -> Dict[str, list]:
    """Greedy ``model.generate`` on the drill model — the token-exact
    anchor every survivor is compared against."""
    import jax.numpy as jnp
    import numpy as np
    from ._drill_worker import build_model
    model = build_model(cfg)
    refs = {}
    for rec in trace:
        ids = jnp.asarray(np.asarray(rec["prompt"], np.int32)[None])
        refs[rec["rid"]] = np.asarray(model.generate(
            ids, max_new_tokens=rec["max_new_tokens"]))[0].tolist()
    return refs


def run_serve_drill(workdir: str, **overrides: Any) -> Dict[str, Any]:
    """Run the fault-injected serving drill and verify exactly-once +
    token-exactness. Returns the full report; ``ok`` is the verdict."""
    from ..distributed.launch import LaunchConfig, launch
    from ..fault.injection import FaultEvent, FaultPlan

    cfg = quick_serve_config()
    cfg.update(overrides)
    os.makedirs(workdir, exist_ok=True)
    trace = _write_trace(os.path.join(workdir, "trace.jsonl"), cfg)
    plan = FaultPlan([FaultEvent(k, int(s)) for k, s in cfg["events"]])

    env = dict(os.environ)
    env.update({
        "FLAGS_flight_recorder": "on",  # arm the worker's black box
        "FLAGS_fleet_telemetry": "on",  # arm the live telemetry plane
        "FLAGS_fleet_export_interval": "0.2",
        "SERVE_WORK_DIR": workdir,
        "SERVE_PLAN": plan.to_json(),
        "SERVE_CFG": json.dumps({k: v for k, v in cfg.items()
                                 if k != "events"}),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    launch_cfg = LaunchConfig(nproc_per_node=1,
                              log_dir=os.path.join(workdir, "logs"),
                              envs=env)
    t0 = time.perf_counter()
    rc = launch(launch_cfg, WORKER, max_restarts=len(plan) + 2,
                elastic_dir=os.path.join(workdir, "hb"))
    wall_s = time.perf_counter() - t0

    report: Dict[str, Any] = {
        "rc": rc, "wall_s": round(wall_s, 4),
        "plan": json.loads(plan.to_json()),
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in cfg.items()},
    }
    fired = []
    try:
        with open(os.path.join(workdir, "fired.json")) as f:
            fired = sorted(json.load(f))
    except (OSError, ValueError):
        pass
    report["fired_events"] = fired
    if rc != 0:
        report["error"] = f"serve drill worker pod exited rc={rc}"
        report["ok"] = False
        return report

    journal = RequestJournal(os.path.join(workdir, "journal.jsonl"))
    expected = [rec["rid"] for rec in trace]
    once = journal.exactly_once_report(expected)
    report["exactly_once"] = once
    report["restarts"] = max(0, once["launches"] - 1)

    # token-exactness: journal outputs (prompt + generated) vs generate
    refs = _reference_outputs(trace, cfg)
    outs = journal.done_outputs()
    prompts = {rec["rid"]: rec["prompt"] for rec in trace}
    mismatched = [rid for rid, toks in outs.items()
                  if prompts[rid] + toks != refs[rid]]
    report["served"] = len(outs)
    report["token_exact"] = not mismatched
    report["mismatched_rids"] = mismatched

    # postmortem reconstruction from the worker's black boxes + the
    # journals: fired kinds/counters must match the plan and every
    # recorder-served output must carry a journaled ack
    from ..observability import fleet
    report["postmortem"] = fleet.postmortem_report(
        workdir, plan=report["plan"]["events"], expected_rids=expected)

    # live fleet plane cross-check: the drill worker exported snapshots
    # under workdir/fleet the whole time (FLAGS_fleet_telemetry=on) —
    # the final incarnation must have said a closed farewell ("exited"),
    # every killed incarnation must have gone silent without one, and
    # the live goodput ratio must agree with the journal reconstruction
    report["fleet"] = _fleet_section(workdir, journal)
    report["ok"] = bool(
        once["exactly_once"] and not mismatched
        and len(fired) == len(plan)
        and report["restarts"] == len(plan)
        and report["postmortem"]["ok"]
        and report["fleet"]["ok"])
    return report


def _fleet_section(workdir: str, journal: RequestJournal) -> Dict[str, Any]:
    """Drill-end live-plane verdict from the exported snapshots."""
    from ..observability import alerts as fleet_alerts
    from ..observability import live as fleet_live
    view = fleet_live.aggregate(workdir)
    engine = fleet_alerts.AlertEngine(fleet_alerts.default_rules(),
                                      emit_mode="off")
    fired_alerts = engine.evaluate(view)
    worker = view["workers"].get("server.r0", {})
    silent = list(worker.get("silent_incarnations", []))
    if worker and worker.get("status") == "dead":
        silent.append(int(worker.get("incarnation", 0)))
    # live goodput = ok acks / all acks over every incarnation's
    # exported counters; the journal's ack mix is the exact postmortem
    # number it must match (a SIGKILL between an ack and the next
    # export may lag the live *counts*, never the final incarnation's,
    # and the quick drill's remainder all lands there)
    live_gp = view["derived"].get("live_goodput")
    outcomes = journal.ack_outcomes()
    pm_gp = (sum(1 for o in outcomes.values() if o == "done")
             / len(outcomes)) if outcomes else None
    match = (live_gp is not None and pm_gp is not None
             and abs(live_gp - pm_gp) < 1e-9)
    return {
        "workers": {k: w["status"] for k, w in view["workers"].items()},
        "incarnations_seen": int(worker.get("incarnations", 0)),
        "silent_incarnations": silent,
        "final_status": worker.get("status"),
        "live_goodput": live_gp,
        "postmortem_goodput": pm_gp,
        "goodput_match": match,
        "derived": view["derived"],
        "alerts": [a.to_json() for a in fired_alerts],
        "ok": bool(worker) and worker.get("status") == "exited"
        and match,
    }


def run_overload_drill(workdir: str, **overrides: Any) -> Dict[str, Any]:
    """The injected-overload drill: an in-process tiny engine under a
    :class:`~paddle_tpu.serving.resilience.ShedPolicy` is offered more
    work than the paged pool tolerates while the live exporter publishes
    snapshots — the aggregated fleet view must show the sheds and the
    default shed-rate SLO rule (L002) must fire from the exported
    history alone.

    Unlike :func:`run_serve_drill` this never forks: the exporter is
    armed in this process (thread off; explicit ``export_now`` before
    and after ``serve`` brackets the overload window), so the alert
    evaluates a *rate* — registry counters are process-lifetime
    cumulative and other engines may have shed before us, but the
    window delta is exactly this drill's. Returns the report;
    ``ok`` requires sheds > 0, the L002 firing, and the live window
    goodput matching the engine's own outcome mix."""
    import numpy as np

    from ..core.flags import get_flags, set_flags
    from ..observability import alerts as fleet_alerts
    from ..observability import live as fleet_live
    from ._drill_worker import build_model
    from .engine import ServingEngine
    from .resilience import Rejected, ShedPolicy
    from .scheduler import Request, Status

    cfg = quick_serve_config()
    cfg.update(requests=10, events=(), shed_free_frac=0.5)
    cfg.update(overrides)
    os.makedirs(workdir, exist_ok=True)
    trace = _write_trace(os.path.join(workdir, "trace.jsonl"), cfg)

    prev = get_flags(["fleet_telemetry", "fleet_export_interval"])
    set_flags({"fleet_telemetry": "on", "fleet_export_interval": 0.05})
    try:
        exporter = fleet_live.arm(workdir, role="server",
                                  start_thread=False)
        model = build_model(cfg)
        engine = ServingEngine(
            model, block_size=cfg["block_size"],
            num_blocks=cfg["num_blocks"], max_batch=cfg["max_batch"],
            max_seq_len=cfg["max_pos"],
            shed_policy=ShedPolicy(
                min_free_block_frac=float(cfg["shed_free_frac"])))
        requests = [Request(rid=rec["rid"],
                            prompt_ids=np.asarray(rec["prompt"], np.int32),
                            max_new_tokens=int(rec["max_new_tokens"]))
                    for rec in trace]
        exporter.export_now()           # baseline sample: counters before
        done = engine.serve(requests)
        exporter.export_now()           # post sample: the overload delta
        fleet_live.disarm(final_export=True)
    finally:
        fleet_live.disarm(final_export=False)  # no-op on the clean path
        set_flags(prev)

    # engine truth for the window: the drill's own outcome mix
    outcomes = {"ok": 0, "shed": 0, "rejected": 0, "expired": 0,
                "failed": 0}
    for res in done.values():
        if isinstance(res, Rejected):
            outcomes["rejected"] += 1
        elif res.status is Status.FINISHED:
            outcomes["ok"] += 1
        else:
            outcomes[res.status.value] += 1

    view = fleet_live.aggregate(workdir)
    alert_engine = fleet_alerts.AlertEngine(
        fleet_alerts.default_rules(
            min_free_block_frac=float(cfg["shed_free_frac"])),
        emit_mode="off")
    fired = alert_engine.evaluate(view)
    worker = view["workers"].get("server.r0", {})

    # live window goodput: first vs last exported sample (delta over the
    # overload bracket — immune to whatever this process served before)
    hist = worker.get("history", [])
    deltas: Dict[str, float] = {}
    if len(hist) >= 2:
        for k in outcomes:
            deltas[k] = float(hist[-1].get(k, 0) or 0) \
                - float(hist[0].get(k, 0) or 0)
    acks = sum(deltas.values()) if deltas else 0.0
    live_gp = (deltas.get("ok", 0.0) / acks) if acks else None
    truth_acks = sum(outcomes.values())
    truth_gp = (outcomes["ok"] / truth_acks) if truth_acks else None
    gp_match = (live_gp is not None and truth_gp is not None
                and abs(live_gp - truth_gp) < 1e-9)

    shed_alert = any(a.rule == "shed-rate" for a in fired)
    report = {
        "requests": len(trace),
        "outcomes": outcomes,
        "window_deltas": deltas,
        "live_goodput": live_gp,
        "engine_goodput": truth_gp,
        "goodput_match": gp_match,
        "final_status": worker.get("status"),
        "derived": view["derived"],
        "alerts": [a.to_json() for a in fired],
        "shed_alert_fired": shed_alert,
        "ok": bool(outcomes["shed"] > 0 and shed_alert and gp_match
                   and worker.get("status") == "exited"),
    }
    with open(os.path.join(workdir, "overload_report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True, default=str)
    return report


def report_summary(report: Dict[str, Any]) -> str:
    once = report.get("exactly_once", {})
    lines = [
        f"serve drill rc={report.get('rc')} ok={report.get('ok')} "
        f"wall={report.get('wall_s')}s",
        f"  plan:  {[e['kind'] + '@' + str(e['step']) for e in report['plan']['events']]}",
        f"  fired: {report.get('fired_events')} "
        f"(restarts={report.get('restarts')})",
        f"  requests: {once.get('expected')} expected, "
        f"{once.get('acknowledged')} acknowledged, "
        f"lost={once.get('lost')}, duplicated={once.get('duplicated')}",
        f"  outputs: {report.get('served')} served, "
        f"token_exact={report.get('token_exact')}",
    ]
    pm = report.get("postmortem")
    if pm:
        lines.append(
            f"  postmortem: ok={pm.get('ok')} "
            f"coherent={pm.get('coherent')} "
            f"recorder_files={pm.get('recorder_files')} "
            f"deaths={[(d['kind'], d['step']) for d in pm.get('deaths', [])]}")
    fl = report.get("fleet")
    if fl:
        lines.append(
            f"  fleet: final={fl.get('final_status')} "
            f"silent_incs={fl.get('silent_incarnations')} "
            f"goodput live={fl.get('live_goodput')} "
            f"pm={fl.get('postmortem_goodput')} "
            f"match={fl.get('goodput_match')} "
            f"alerts={[a['rule'] for a in fl.get('alerts', [])]}")
    return "\n".join(lines)
