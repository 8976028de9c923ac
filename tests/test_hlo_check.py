"""Compiled-HLO verifier (analysis/hlo_check.py): each X-rule fires on
exactly its seeded fault and stays silent on the clean compiled steps —
including the ISSUE 11 acceptance pair (realized donations on both the
sharded TrainStep and a serving decode-bucket executable) and an
in-process tier-flag matrix subset with the X pass on."""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.analysis import _hlo_utils, hlo_check, plan_check
from paddle_tpu.analysis._hlo_utils import aot_compile
from paddle_tpu.analysis.plan_check import StepPlan
from paddle_tpu.core import flags as core_flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rules_of(diags):
    return {d.rule for d in diags}


def errors_of(diags):
    return [d for d in diags if d.severity == "error"]


def _mesh2x4():
    return Mesh(np.asarray(jax.devices()).reshape(2, 4), ("slice", "dp"))


# ---------------------------------------------------------------------------
# _hlo_utils: parsing
# ---------------------------------------------------------------------------

SYNTH_HLO = """\
HloModule jit_f, is_scheduled=true, input_output_alias={ {1}: (0, {}, \
may-alias), {2}: (3, {}, may-alias) }, num_partitions=8

%region_1.4 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)
}

%body.9 (arg: (s32[], f32[2,2])) -> (s32[], f32[2,2]) {
  %arg = (s32[], f32[2,2]) parameter(0)
  %gte.1 = f32[2,2]{1,0} get-tuple-element((s32[], f32[2,2]) %arg), index=1
  %all-reduce.7 = f32[2,2]{1,0} all-reduce(f32[2,2]{1,0} %gte.1), \
channel_id=1, replica_groups={{0,4},{1,5},{2,6},{3,7}}, \
use_global_device_ids=true, to_apply=%region_1.4
  ROOT %tuple.2 = (s32[], f32[2,2]) tuple(s32[] %gte.1, %all-reduce.7)
}

%cond.20 (arg2: (s32[], f32[2,2])) -> pred[] {
  %arg2 = (s32[], f32[2,2]) parameter(0)
  ROOT %lt = pred[] compare(s32[] %arg2, s32[] %arg2), direction=LT
}

ENTRY %main.30 (p0: f32[2,2], p1: f32[2,2]) -> (f32[2,2], f32[2,2]) {
  %p0 = f32[2,2]{1,0} parameter(0)
  %p1 = f32[2,2]{1,0} parameter(1)
  %convert.1 = bf16[2,2]{1,0} convert(f32[2,2]{1,0} %p0)
  %convert.2 = f32[2,2]{1,0} convert(bf16[2,2]{1,0} %convert.1)
  %wide.1 = f64[2,2]{1,0} convert(f32[2,2]{1,0} %p1)
  %tuple.3 = (s32[], f32[2,2]) tuple(s32[] %p0, f32[2,2]{1,0} %p1)
  %while.1 = (s32[], f32[2,2]) while((s32[], f32[2,2]) %tuple.3), \
condition=%cond.20, body=%body.9
  %all-gather.3 = f32[2,8]{1,0} all-gather(f32[2,2]{1,0} %p1), \
channel_id=3, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={1}
  ROOT %out = (f32[2,2], f32[2,2]) tuple(%convert.2, %p1)
}
"""


def test_parse_hlo_synthetic():
    mod = _hlo_utils.parse_hlo(SYNTH_HLO)
    assert mod.entry == "main.30"
    assert (0, "") in mod.aliases and (3, "") in mod.aliases
    # while body + its to_apply reducer are loop computations
    assert "body.9" in mod.loop_computations
    assert "region_1.4" in mod.loop_computations
    assert "main.30" not in mod.loop_computations
    ops = {i.op for i in mod.instructions()}
    assert {"all-reduce", "all-gather", "while", "convert"} <= ops


def test_collect_facts_synthetic():
    facts = hlo_check.collect_hlo_facts(SYNTH_HLO)
    assert facts.collectives == {"all-reduce": 1, "all-gather": 1}
    # the all-reduce sits in the while body, with its groups parsed
    assert len(facts.loop_collectives) == 1
    kind, groups = facts.loop_collectives[0]
    assert kind == "all-reduce" and [0, 4] in groups
    assert len(facts.aliases) == 2
    assert facts.f64_values == 1          # %wide.1
    assert facts.convert_chains == 1      # f32 -> bf16 -> f32
    assert facts.memory is None           # text input: no memory_analysis


def test_aot_compile_paths():
    """aot_compile accepts plain callables AND pre-jitted functions (the
    cost_model/utils call shapes)."""
    f = lambda x: x * 2  # noqa: E731
    x = jnp.ones((4,))
    c1 = aot_compile(f, x)
    c2 = aot_compile(jax.jit(f), x)
    assert _hlo_utils.cost_dict(c1).keys() == _hlo_utils.cost_dict(c2).keys()
    assert np.allclose(np.asarray(c1(x)), 2.0)


# ---------------------------------------------------------------------------
# X001 — undeclared compiled collective
# ---------------------------------------------------------------------------

def _sneaky_resharding_compiled():
    """Replicated params, an intermediate pinned onto a mesh axis: GSPMD
    must gather it back — a compiled all-gather the jaxpr never shows."""
    mesh = _mesh2x4()
    repl = NamedSharding(mesh, P())

    def f(w, x):
        h = jax.lax.with_sharding_constraint(
            x @ w, NamedSharding(mesh, P(None, "dp")))
        return jnp.tanh(h) @ w

    return jax.jit(f, in_shardings=(repl, repl), out_shardings=repl).lower(
        jnp.ones((16, 16)), jnp.ones((8, 16))).compile()


def test_x001_fires_on_undeclared_resharding_gather():
    compiled = _sneaky_resharding_compiled()
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4})  # nothing sharded
    diags = hlo_check.check_hlo(plan, compiled)
    assert "X001" in rules_of(errors_of(diags))
    facts = hlo_check.collect_hlo_facts(compiled)
    assert facts.collectives.get("all-gather", 0) >= 1


def test_x001_negative_when_plan_declares_sharding():
    """The same module is justified once the plan declares sharded
    params (fsdp axis): GSPMD gather-class movement is expected."""
    compiled = _sneaky_resharding_compiled()
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4}, fsdp_axis="dp")
    assert "X001" not in rules_of(hlo_check.check_hlo(plan, compiled))


def test_x001_negative_comm_spec_justifies_kind():
    """A declared CommSpec justifies exactly the kinds its decomposition
    lowers to (SPEC_KINDS)."""
    from paddle_tpu.analysis import comm_check
    compiled = _sneaky_resharding_compiled()
    spec = comm_check.spec_for_slice_all_gather(1 << 20, 4)
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4},
                    comm_specs=[("test", spec)])
    assert "X001" not in rules_of(hlo_check.check_hlo(plan, compiled))


def test_x001_no_mesh_plan_justifies_nothing():
    """A plan with no mesh (the serving engine's executables) treats ANY
    compiled collective as a finding."""
    facts = hlo_check.HloFacts(collectives={"all-reduce": 1})
    diags = hlo_check.check_hlo(StepPlan(), facts)
    assert "X001" in rules_of(diags)
    # all-to-all is never implicit, even on a declared multi-axis mesh
    facts = hlo_check.HloFacts(collectives={"all-to-all": 2})
    plan = StepPlan(mesh_axes={"dp": 8}, fsdp_axis="dp")
    assert "X001" in rules_of(hlo_check.check_hlo(plan, facts))


# ---------------------------------------------------------------------------
# X002 — donation realization (incl. the ISSUE acceptance pair)
# ---------------------------------------------------------------------------

def test_x002_fires_on_unrealized_donation():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own "donated buffers" note
        compiled = aot_compile(lambda a: a.sum(), jnp.ones((64, 64)),
                               donate_argnums=(0,))
    diags = hlo_check.check_hlo(StepPlan(), compiled, donated_leaves=1)
    assert "X002" in rules_of(errors_of(diags))


def test_x002_negative_realized_donation():
    compiled = aot_compile(lambda a: a + 1, jnp.ones((64, 64)),
                           donate_argnums=(0,))
    diags = hlo_check.check_hlo(StepPlan(), compiled, donated_leaves=1)
    assert "X002" not in rules_of(diags)


def test_x002_partial_realization_warns():
    facts = hlo_check.HloFacts(aliases=[(0, "")])
    diags = hlo_check.check_hlo(StepPlan(), facts, donated_leaves=3)
    hit = [d for d in diags if d.rule == "X002"]
    assert hit and hit[0].severity == "warning"


def test_x002_acceptance_train_step_donation_realized():
    """ISSUE 11 acceptance: the sharded TrainStep's declared donation is
    realized — every donated param/opt-state leaf aliases an output in
    the compiled module, and the whole module is X-clean."""
    from paddle_tpu import nn
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
    batch = (jnp.zeros((8, 8), jnp.float32), jnp.zeros((8,), jnp.int32))
    compiled, donated = ts.compile_step(batch)
    assert donated == (len(jax.tree_util.tree_leaves(ts.params))
                       + len(jax.tree_util.tree_leaves(ts.opt_state)))
    facts = hlo_check.collect_hlo_facts(compiled)
    assert len({a[0] for a in facts.aliases}) == donated
    diags = hlo_check.check_hlo(ts.plan, facts, donated_leaves=donated)
    assert diags == [], [d.format() for d in diags]


def test_x002_acceptance_serving_decode_donation_realized():
    """ISSUE 11 acceptance: the serving decode-bucket executable realizes
    both page-pool donations and compiles with zero collectives."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    cfg = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2,
                   num_heads=2, max_position_embeddings=32)
    eng = ServingEngine(GPTForCausalLM(cfg), block_size=4, num_blocks=16,
                        max_batch=2)
    compiled, donated = eng.compile_decode()
    facts = hlo_check.collect_hlo_facts(compiled)
    assert donated == 2
    assert len({a[0] for a in facts.aliases}) == 2
    assert facts.collectives == {}
    diags = hlo_check.check_hlo(eng.plan, facts, donated_leaves=donated)
    assert diags == [], [d.format() for d in diags]


# ---------------------------------------------------------------------------
# X003 — compiled peak vs the static envelope
# ---------------------------------------------------------------------------

def test_x003_fires_when_peak_exceeds_envelope():
    compiled = aot_compile(lambda a: a @ a, jnp.ones((128, 128)))
    cap = {"budget_gb": 1e-6, "fits": True}
    diags = hlo_check.check_hlo(StepPlan(), compiled, capacity=cap)
    assert "X003" in rules_of(errors_of(diags))


def test_x003_negative_within_envelope_and_without_capacity():
    compiled = aot_compile(lambda a: a @ a, jnp.ones((128, 128)))
    diags = hlo_check.check_hlo(StepPlan(), compiled,
                                capacity={"budget_gb": 15.75})
    assert "X003" not in rules_of(diags)
    # no capacity plan declared -> the rule stays out of the way
    assert "X003" not in rules_of(hlo_check.check_hlo(StepPlan(), compiled))


# ---------------------------------------------------------------------------
# X004 — dtype churn
# ---------------------------------------------------------------------------

def test_x004_fires_on_f64_in_compiled_module():
    with jax.enable_x64(True):
        compiled = aot_compile(lambda a: a.astype(jnp.float64).sum(),
                               jnp.ones((8,), jnp.float32))
    diags = hlo_check.check_hlo(StepPlan(), compiled)
    assert "X004" in rules_of(errors_of(diags))


def test_x004_convert_round_trip_warns():
    compiled = aot_compile(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32) + 1.0,
        jnp.ones((128, 128)))
    hit = [d for d in hlo_check.check_hlo(StepPlan(), compiled)
           if d.rule == "X004"]
    assert hit and hit[0].severity == "warning"


def test_x004_negative_clean_f32():
    compiled = aot_compile(lambda a: jnp.tanh(a) @ a, jnp.ones((64, 64)))
    assert "X004" not in rules_of(hlo_check.check_hlo(StepPlan(), compiled))


def test_x004_negative_staged_cast_not_churn():
    """f32 -> bf16 -> f32 is churn; i32 -> f32 -> bf16 (a->b->c) is a
    legitimate staged cast and must not fire."""
    compiled = aot_compile(
        lambda a: (a.astype(jnp.float32) / 3).astype(jnp.bfloat16),
        jnp.ones((64,), jnp.int32))
    assert "X004" not in rules_of(hlo_check.check_hlo(StepPlan(), compiled))


# ---------------------------------------------------------------------------
# X005 — DCN collective in a compiled loop body
# ---------------------------------------------------------------------------

def _loop_psum_compiled(axis):
    mesh = _mesh2x4()

    def inner(x):
        def body(c, _):
            # psum makes the carry invariant over `axis`; pvary restores
            # the carry's varying-axes type so the scan typechecks
            return jax.lax.pcast(jax.lax.psum(c, axis), axis,
                                  to="varying") * 0.5, ()
        return jax.lax.scan(body, x, None, length=3)[0]

    f = jax.shard_map(inner, mesh=mesh, in_specs=P("slice", "dp"),
                      out_specs=P("slice", "dp"))
    return aot_compile(f, jnp.ones((4, 8)))


def test_x005_fires_on_dcn_collective_in_while_body():
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4})
    diags = hlo_check.check_hlo(plan, _loop_psum_compiled("slice"))
    hit = [d for d in diags if d.rule == "X005"]
    assert hit and hit[0].severity == "warning"


def test_x005_negative_ici_collective_in_loop():
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4})
    diags = hlo_check.check_hlo(plan, _loop_psum_compiled("dp"))
    assert "X005" not in rules_of(diags)


def test_x005_negative_without_mesh_info():
    """No declared mesh -> device coordinates are unknowable; the rule
    declines to guess (X001 still covers the undeclared collective)."""
    diags = hlo_check.check_hlo(StepPlan(), _loop_psum_compiled("slice"))
    assert "X005" not in rules_of(diags)


# ---------------------------------------------------------------------------
# Wiring: FLAGS channel, TrainStep first-step lint, matrix subset
# ---------------------------------------------------------------------------

@pytest.fixture
def analysis_error_mode():
    core_flags.set_flags({"static_analysis": "error"})
    yield
    core_flags.set_flags({"static_analysis": "off"})


def test_enforce_routes_through_flags_channel(analysis_error_mode):
    from paddle_tpu.analysis.jaxpr_lint import GraphLintError
    compiled = _sneaky_resharding_compiled()
    plan = StepPlan(mesh_axes={"slice": 2, "dp": 4})
    with pytest.raises(GraphLintError) as ei:
        hlo_check.enforce(plan, compiled, where="test")
    assert "X001" in str(ei.value)


def test_train_step_first_dispatch_lints_hlo_clean(analysis_error_mode):
    """The TrainStep._maybe_lint final stage (compile + X-rules) stays
    silent on a clean step even in error mode — and the step still runs."""
    from paddle_tpu import nn
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
    batch = (jnp.zeros((8, 8), jnp.float32), jnp.zeros((8,), jnp.int32))
    loss = ts.step(batch)
    assert np.isfinite(float(loss))
    assert ts._linted


def test_matrix_subset_x_rules_silent(capsys):
    """An in-process --matrix subset with the compiled-HLO pass on: the
    X-rules stay silent across tier-flag combos and the report carries
    the per-step hlo facts + schema v2 fields."""
    import json
    from tools import lint_graph

    combos = [
        {"offload_optimizer": "off", "comm_overlap": "off",
         "multislice": "off", "cp_nested_ring": False, "pallas_conv": 0,
         "remat": False},
        {"offload_optimizer": "moments", "comm_overlap": "off",
         "multislice": "off", "cp_nested_ring": False, "pallas_conv": 0,
         "remat": True},
    ]
    rc = lint_graph.run_matrix(json_mode=True, with_dryrun=False,
                               combos=combos, with_hlo=True)
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["errors"] == 0
    assert report["schema_version"] == lint_graph.SCHEMA_VERSION
    assert "rule_index" in report
    for entry in report["combos"]:
        hlo = entry["step"]["hlo"]
        assert hlo["aliases"] >= 0 and "collectives" in hlo
        assert not any(d["rule"].startswith("X")
                       for d in entry["diagnostics"]), entry["diagnostics"]
    # the offloaded grad step donates nothing; the plain step aliases
    plain, offl = report["combos"]
    assert plain["step"]["hlo"]["aliases"] > 0


def test_lint_graph_json_rule_index(capsys):
    """--json schema v2: schema_version + family -> {count, ids} index."""
    import json
    from tools import lint_graph
    rc = lint_graph.run(["mlp"], json_mode=True)
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["schema_version"] == lint_graph.SCHEMA_VERSION
    for fam, entry in report["rule_index"].items():
        assert len(fam) == 1
        assert entry["count"] == sum(entry["ids"].values())


def test_hlo_rules_registered():
    ids = {r.rule_id for r in hlo_check.all_hlo_rules()}
    assert ids == {"X001", "X002", "X003", "X004", "X005"}
    assert all(r.doc for r in hlo_check.all_hlo_rules())
