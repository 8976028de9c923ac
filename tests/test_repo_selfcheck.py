"""Repo self-check: the static-analysis gates run over the repo itself, so
new rules (J013, O0xx) and new subsystems (paddle_tpu/observability/) gate
each other — a lint rule that the repo's own code trips fails CI here, and
an observability module with a banned idiom (host clock in a kernel, flag
registry bypass, constant seed) fails the same way."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_lint_graph_all_exits_zero(capsys):
    """`tools/lint_graph.py --all` — every example model graph, the Pallas
    kernel configs, and the AST repo lint — must stay error-free."""
    from tools import lint_graph
    rc = lint_graph.run(sorted(lint_graph.MODELS), with_kernels=True,
                        with_repo=True, min_severity="info")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 error(s)" in out


def test_repo_lint_clean_over_observability():
    """The new subsystem passes the source rules it sits next to (R001
    host clocks are fine here — observability is not a kernel module — but
    R002/R003 apply in full)."""
    from paddle_tpu.analysis import repo_lint
    diags = repo_lint.lint_tree(REPO, subdir=os.path.join(
        "paddle_tpu", "observability"))
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]


def test_observability_graphs_have_no_callbacks():
    """J013 self-application: the instrumented train step compiles no host
    callbacks — telemetry is dispatch-level by construction."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.analysis import lint_fn
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.nn import functional as F
    from paddle_tpu.optimizer import AdamW

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))

    def loss_fn(model, params, batch):
        x, y = batch
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    ts = make_sharded_train_step(net, AdamW(1e-3), loss_fn)
    import jax.numpy as jnp
    batch = (jnp.zeros((8, 8), jnp.float32), jnp.zeros((8,), jnp.int32))
    key = jax.random.key(0)
    lr = jnp.float32(1e-3)
    diags = lint_fn(ts._step_fn, ts.params, ts.opt_state, ts.buffers,
                    batch, lr, key, where="selfcheck")
    assert "J013" not in {d.rule for d in diags}


def test_telemetry_flag_registered():
    """FLAGS_telemetry goes through the registry (R003 would catch a
    bypass; this catches a typo'd default)."""
    from paddle_tpu.core import flags
    assert flags.flag("telemetry") in ("off", "metrics", "trace")
    with pytest.raises(ValueError):
        flags.set_flags({"telemetry": "verbose"})
    assert "telemetry" not in flags.unknown_env_flags()


def test_repo_lint_clean_over_overlap_tier():
    """The comm-overlap tier sources (distributed/overlap.py,
    analysis/comm_check.py) pass the repo source rules. R001 host clocks
    are allowed only at the annotated autotune timing sites."""
    from paddle_tpu.analysis import repo_lint
    for rel in (os.path.join("paddle_tpu", "distributed", "overlap.py"),
                os.path.join("paddle_tpu", "analysis", "comm_check.py")):
        diags = repo_lint.lint_file(os.path.join(REPO, rel), rel)
        errors = [d for d in diags if d.severity == "error"]
        assert errors == [], [d.format() for d in errors]


def test_overlap_model_in_lint_graph_catalog():
    """`tools/lint_graph.py --model overlap` exists and the decomposed
    programs lint with zero errors (J012/J013/J014 + C0xx accounting)."""
    from tools import lint_graph
    assert "overlap" in lint_graph.MODELS
    diags, n_eqns = lint_graph.MODELS["overlap"]()
    assert n_eqns > 0, "overlap model must trace on the 8-device mesh"
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]
    assert "J014" not in {d.rule for d in diags}, \
        "the decomposed pipelines must not trip the rule they motivated"


def test_comm_overlap_flags_registered():
    """FLAGS_comm_overlap and its knobs go through the registry."""
    from paddle_tpu.core import flags
    assert flags.flag("comm_overlap") in ("off", "tp", "tp_zero", "all")
    with pytest.raises(ValueError):
        flags.set_flags({"comm_overlap": "everything"})
    assert int(flags.flag("comm_overlap_bucket_mb")) > 0


def test_rules_md_catalog_matches_code():
    """Meta-test: every rule id registered/emitted anywhere in the code
    appears in analysis/RULES.md's per-family tables, and every id the
    catalog documents exists in code — the catalog cannot silently rot."""
    import glob
    import re
    from paddle_tpu.analysis import (concurrency_check, hlo_check,
                                     jaxpr_lint, pass_check, plan_check)

    code_ids = {r.rule_id for r in jaxpr_lint.all_rules()}
    code_ids |= {r.rule_id for r in plan_check.all_plan_rules()}
    code_ids |= {r.rule_id for r in hlo_check.all_hlo_rules()}
    code_ids |= {r.rule_id for r in concurrency_check.all_thread_rules()}
    code_ids |= {r.rule_id for r in pass_check.all_pass_rules()}
    sources = (
        glob.glob(os.path.join(REPO, "paddle_tpu", "analysis", "*.py")) +
        glob.glob(os.path.join(REPO, "paddle_tpu", "observability",
                               "*.py")) +
        glob.glob(os.path.join(REPO, "paddle_tpu", "fault", "*.py")) +
        glob.glob(os.path.join(REPO, "paddle_tpu", "serving", "*.py")) +
        [os.path.join(REPO, "paddle_tpu", "inference", "__init__.py"),
         os.path.join(REPO, "paddle_tpu", "amp", "debugging.py"),
         os.path.join(REPO, "paddle_tpu", "jit", "dy2static.py"),
         os.path.join(REPO, "paddle_tpu", "profiler", "statistic.py"),
         os.path.join(REPO, "paddle_tpu", "distributed", "fleet",
                      "elastic", "__init__.py")])
    emit_pat = re.compile(r'''rule=["']([A-Z]\d{3})["']''')
    call_pat = re.compile(r'''add\(["']([A-Z]\d{3})["']''')
    for path in sources:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        code_ids.update(emit_pat.findall(src))
        code_ids.update(call_pat.findall(src))

    md_path = os.path.join(REPO, "paddle_tpu", "analysis", "RULES.md")
    with open(md_path, encoding="utf-8") as f:
        md = f.read()
    md_ids = set(re.findall(r"^\| ([A-Z]\d{3}) \|", md, re.MULTILINE))

    missing_from_md = sorted(code_ids - md_ids)
    missing_from_code = sorted(md_ids - code_ids)
    assert not missing_from_md, \
        f"rules registered in code but absent from RULES.md: " \
        f"{missing_from_md}"
    assert not missing_from_code, \
        f"rules documented in RULES.md but absent from code: " \
        f"{missing_from_code}"


def test_plan_rules_registered():
    """The S/D families are registry-enumerable (the matrix gate and the
    meta-test both rely on it)."""
    from paddle_tpu.analysis import plan_check
    ids = {r.rule_id for r in plan_check.all_plan_rules()}
    assert ids == {"S001", "S002", "S003", "D001", "D002", "D003", "D004",
                   "D005"}
    assert all(r.doc for r in plan_check.all_plan_rules())


def test_pass_rules_registered():
    """The G family (pass-composition rules) is registry-enumerable,
    lives in its own registry (plan_check's stays pinned), and every
    rule carries a doc line for the RULES.md meta-test."""
    from paddle_tpu.analysis import pass_check
    ids = {r.rule_id for r in pass_check.all_pass_rules()}
    assert ids == {"G001", "G002", "G003", "G004", "G005"}
    assert all(r.doc for r in pass_check.all_pass_rules())



def test_repo_lint_default_coverage_is_wide():
    """The self-lint gate runs over paddle_tpu/ + tools/ + examples/ +
    __graft_entry__.py and stays error-free."""
    from paddle_tpu.analysis import repo_lint
    diags = repo_lint.lint_tree(REPO)
    linted = {d.source.split(":")[0] for d in diags}
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]
    # tools/examples sources ARE part of the sweep (finding-free, but
    # walked): plant nothing — instead assert the walker visits them via
    # the DEFAULT_SUBTREES contract
    assert "tools" in repo_lint.DEFAULT_SUBTREES
    assert "examples" in repo_lint.DEFAULT_SUBTREES
    del linted


def test_lint_graph_json_report(capsys):
    """--json: stdout is one parseable report, narration on stderr."""
    import json as _json
    from tools import lint_graph
    rc = lint_graph.run(["mlp"], json_mode=True)
    report = _json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["errors"] == 0
    assert "mlp" in report["models"]
    assert isinstance(report["models"]["mlp"]["diagnostics"], list)


def test_repo_lint_clean_over_serving_tier():
    """The serving tier sources (paddle_tpu/serving/, the reworked
    inference predictor, the request timeline) pass the repo source
    rules — a serving module with a constant PRNG seed or a flag-registry
    bypass fails here."""
    from paddle_tpu.analysis import repo_lint
    diags = repo_lint.lint_tree(REPO, subdir=os.path.join(
        "paddle_tpu", "serving"))
    diags += repo_lint.lint_file(
        os.path.join(REPO, "paddle_tpu", "inference", "__init__.py"),
        os.path.join("paddle_tpu", "inference", "__init__.py"))
    diags += repo_lint.lint_file(
        os.path.join(REPO, "paddle_tpu", "observability",
                     "request_timeline.py"),
        os.path.join("paddle_tpu", "observability", "request_timeline.py"))
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]


def test_repo_lint_clean_over_multislice_tier():
    """The multi-slice tier sources (distributed/multislice/, the
    link-class comm_check extension) pass the repo source rules."""
    from paddle_tpu.analysis import repo_lint
    diags = repo_lint.lint_tree(REPO, subdir=os.path.join(
        "paddle_tpu", "distributed", "multislice"))
    diags += repo_lint.lint_file(
        os.path.join(REPO, "paddle_tpu", "analysis", "comm_check.py"),
        os.path.join("paddle_tpu", "analysis", "comm_check.py"))
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]


def test_multislice_model_in_lint_graph_catalog():
    """`tools/lint_graph.py --model multislice` exists; the hierarchical
    2-tier TrainStep and its declared hop plan lint with zero errors, and
    the C004 self-test (the naive flat-over-DCN plan must fire) passes."""
    from tools import lint_graph
    from paddle_tpu.core import flags
    assert "multislice" in lint_graph.MODELS
    diags, n_eqns = lint_graph.MODELS["multislice"]()
    assert n_eqns > 0, "the multislice step must trace on the 2-slice mesh"
    errors = [d for d in diags if d.severity == "error"]
    assert errors == [], [d.format() for d in errors]
    assert "J015" not in {d.rule for d in diags}, \
        "the hierarchical reduction must not trip the rule it motivated"
    assert flags.flag("multislice") == "off", \
        "the lint model must restore FLAGS_multislice"


def test_multislice_flags_registered():
    from paddle_tpu.core import flags
    import pytest as _pytest
    assert flags.flag("multislice") in ("off", "flat", "hierarchical")
    with _pytest.raises(ValueError):
        flags.set_flags({"multislice": "everything"})
    assert int(flags.flag("multislice_dcn_bucket_mb")) > 0


def test_lint_graph_threads_exits_zero(capsys):
    """`tools/lint_graph.py --threads` — every T rule fires on its
    seeded-positive fixture, the repo sweep is T-clean, and the static
    lock graph is acyclic."""
    from tools import lint_graph
    rc = lint_graph.run_threads(min_severity="info")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 error(s)" in out
    for rule in ("T001", "T002", "T003", "T004", "T005"):
        assert f"{rule}: fires" in out


def test_thread_rules_registered():
    """The T family is registry-enumerable (the meta-test and the
    --threads self-tests both rely on it) and FLAGS_lockcheck goes
    through the flag registry."""
    from paddle_tpu.analysis import concurrency_check
    from paddle_tpu.core import flags
    ids = {r.rule_id for r in concurrency_check.all_thread_rules()}
    assert ids == {"T001", "T002", "T003", "T004", "T005"}
    assert flags.flag("lockcheck") in (True, False)
    assert "lockcheck" not in flags.unknown_env_flags()


def test_lint_graph_threads_json_reports_t_rows(capsys):
    """--threads --json: the schema-v2 report carries the T-family
    rule_index rows CI diffs across PRs (empty when the repo is clean,
    but selftests/lock_graph always present)."""
    import json as _json
    from tools import lint_graph
    rc = lint_graph.run_threads(json_mode=True)
    report = _json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["schema_version"] == lint_graph.SCHEMA_VERSION
    assert report["errors"] == 0
    assert set(report["selftests"]) == \
        {"T001", "T002", "T003", "T004", "T005"}
    assert all(report["selftests"].values())
    assert report["lock_graph"]["cycles"] == []
    assert isinstance(report["rule_index"], dict)


def test_repo_lint_clean_over_flight_recorder_tier():
    """The flight-recorder tier sources (the mmap ring, the fleet
    aggregator, the postmortem CLI) pass the repo source rules — R002/
    R003 apply in full; R001 host clocks are fine (not kernel code, and
    wall-clock timestamps are the cross-incarnation ordering key)."""
    from paddle_tpu.analysis import repo_lint
    for rel in (os.path.join("paddle_tpu", "observability",
                             "flight_recorder.py"),
                os.path.join("paddle_tpu", "observability", "fleet.py"),
                os.path.join("tools", "postmortem.py")):
        diags = repo_lint.lint_file(os.path.join(REPO, rel), rel)
        errors = [d for d in diags if d.severity == "error"]
        assert errors == [], [d.format() for d in errors]


def test_concurrency_check_clean_over_flight_recorder():
    """The recorder's mmap writer is exactly the cross-thread code the
    T rules exist for (the watchdog timer thread, the checkpoint writer
    thread and the training loop all record into one ring): the module
    must stay T001/T003/T004-clean under the static analyzer."""
    from paddle_tpu.analysis import concurrency_check
    path = os.path.join(REPO, "paddle_tpu", "observability",
                        "flight_recorder.py")
    diags = concurrency_check.check_file(
        path, os.path.join("paddle_tpu", "observability",
                           "flight_recorder.py"))
    assert diags == [], [d.format() for d in diags]


def test_flight_recorder_flags_registered():
    """FLAGS_flight_recorder goes through the registry with validated
    choices, like FLAGS_telemetry."""
    from paddle_tpu.core import flags
    assert flags.flag("flight_recorder") in ("off", "on")
    with pytest.raises(ValueError):
        flags.set_flags({"flight_recorder": "maybe"})
    assert int(flags.flag("flight_recorder_mb")) > 0
    assert "flight_recorder" not in flags.unknown_env_flags()


def test_serving_model_in_lint_graph_catalog():
    """`tools/lint_graph.py --model serving` exists; the bucketed
    prefill/decode executables and the declared dispatch plan lint with
    zero findings (J-rules + S/D plan rules)."""
    from tools import lint_graph
    assert "serving" in lint_graph.MODELS
    diags, n_eqns = lint_graph.MODELS["serving"]()
    assert n_eqns > 0, "serving steps must trace"
    assert diags == [], [d.format() for d in diags]


def test_repo_lint_clean_over_fleet_live_tier():
    """The live fleet plane (the per-worker exporter, the SLO rule
    engine, the fleet-top console) passes the repo source rules —
    the exporter thread and the CRC framing are exactly the code R002/
    R003 sweep for; wall-clock timestamps are the staleness key, so
    R001 host clocks are expected and fine."""
    from paddle_tpu.analysis import repo_lint
    for rel in (os.path.join("paddle_tpu", "observability", "live.py"),
                os.path.join("paddle_tpu", "observability", "alerts.py"),
                os.path.join("tools", "fleet_top.py")):
        diags = repo_lint.lint_file(os.path.join(REPO, rel), rel)
        errors = [d for d in diags if d.severity == "error"]
        assert errors == [], [d.format() for d in errors]


def test_concurrency_check_clean_over_fleet_live():
    """The exporter publishes registry snapshots from a daemon thread
    while the training/serving loop mutates the same counters — the
    T-rule analyzer must find nothing in either module."""
    from paddle_tpu.analysis import concurrency_check
    for rel in (os.path.join("paddle_tpu", "observability", "live.py"),
                os.path.join("paddle_tpu", "observability", "alerts.py")):
        diags = concurrency_check.check_file(os.path.join(REPO, rel), rel)
        assert diags == [], [d.format() for d in diags]


def test_fleet_telemetry_flags_registered():
    """FLAGS_fleet_telemetry / FLAGS_fleet_export_interval go through
    the validated registry like every other observability arm."""
    from paddle_tpu.core import flags
    assert flags.flag("fleet_telemetry") in ("off", "on")
    with pytest.raises(ValueError):
        flags.set_flags({"fleet_telemetry": "maybe"})
    assert float(flags.flag("fleet_export_interval")) > 0
    assert "fleet_telemetry" not in flags.unknown_env_flags()
    assert "fleet_export_interval" not in flags.unknown_env_flags()


def test_fleet_top_once_json_smokes_in_process(tmp_path):
    """`fleet_top --once --json` is the CI probe shape: over a live
    export it must exit 0 and print one machine-parseable frame."""
    import io
    import json as _json
    from contextlib import redirect_stdout
    from paddle_tpu.core import flags
    from paddle_tpu.observability import live
    from tools import fleet_top
    prev = flags.get_flags(["fleet_telemetry"])
    flags.set_flags({"fleet_telemetry": "on"})
    try:
        live.arm(str(tmp_path), role="ci", start_thread=False)
        live.note_progress(1)
        live.disarm(final_export=True)
    finally:
        live.disarm(final_export=False)
        flags.set_flags(prev)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = fleet_top.main([str(tmp_path), "--once", "--json",
                             "--fail-on-alert"])
    frame = _json.loads(buf.getvalue())
    assert rc == 0, frame
    assert frame["view"]["workers"]["ci.r0"]["status"] == "exited"
