"""Step-plan verifier (analysis/plan_check.py): clean composed plans stay
silent across the tier-flag combinations; each S/D rule fires on exactly
its seeded fault (ISSUE 6 acceptance criteria)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import comm_check, plan_check
from paddle_tpu.analysis.plan_check import (GatherPlan, ParamInfo, PlanNode,
                                            StepPlan)
from paddle_tpu.core import flags as core_flags
from paddle_tpu.distributed import overlap
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.framework.functional import functional_call
from paddle_tpu.framework.sharded import make_sharded_train_step
from paddle_tpu.optimizer import AdamW

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def rules_of(diags):
    return {d.rule for d in diags}


def errors_of(diags):
    return [d for d in diags if d.severity == "error"]


@pytest.fixture(autouse=True)
def _restore_flags():
    prev = {k: core_flags.flag(k)
            for k in ("offload_optimizer", "comm_overlap",
                      "cp_nested_ring")}
    yield
    core_flags.set_flags(prev)


def _micro_ts(offload="off", comm_overlap="off", remat=False):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    core_flags.set_flags({"offload_optimizer": offload,
                          "comm_overlap": comm_overlap})
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash_attention=False, recompute=remat)
    model = GPTForCausalLM(cfg)
    mesh = create_hybrid_mesh(dp=2, sharding=2, mp=2)
    set_hybrid_mesh(mesh)

    def loss_fn(m, p, b):
        ids, labels = b
        return functional_call(m, p, ids, labels, training=True)

    ts = make_sharded_train_step(model, AdamW(1e-3), loss_fn, mesh=mesh)
    ids = jnp.zeros((4, 16), jnp.int32)
    return ts, (ids, ids)


# ---------------------------------------------------------------------------
# Clean compositions are silent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offload,comm", [
    ("off", "off"), ("off", "tp_zero"), ("moments", "off"),
    ("moments", "all"),
])
def test_clean_composed_plan_is_silent(offload, comm):
    ts, batch = _micro_ts(offload, comm)
    closed, donate = ts.trace_step(batch)
    diags = plan_check.check_plan(ts.plan, closed, donate_argnums=donate)
    assert diags == [], [d.format() for d in diags]


def test_plan_records_composition():
    ts, batch = _micro_ts("moments", "tp_zero")
    assert ts.plan.flags["offload_optimizer"] == "moments"
    assert ts.plan.flags["gather_ahead"] is True
    # grad-only step + per-block streaming nodes, params NOT donated
    assert ts.plan.nodes[0].name == "grad_step"
    assert ts.plan.nodes[0].donates == ()
    assert any(n.name.startswith("offload.update") for n in ts.plan.nodes)
    assert ts.plan.gather is not None and len(ts.plan.gather.params) > 0
    j = ts.plan.to_json()
    assert j["gather"]["depth"] == overlap.GATHER_AHEAD_DEPTH


def test_trace_fills_comm_registry_on_decomposed_path():
    """The SP pair traced under comm_check.recording(): the declared hop
    plans land in the registry keyed by call site, and the cross-check
    against the traced ppermutes is silent."""
    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(1, 1, 1, 1, n),
                ("pp", "dp", "sharding", "sep", "mp"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8 * n, 16)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)

    def loss(x, w1, w2):
        h = overlap.allgather_matmul(x, w1, mesh=mesh, chunks=1)
        y = overlap.matmul_reduce_scatter(jax.nn.gelu(h), w2, mesh=mesh,
                                          chunks=1)
        return jnp.sum(y ** 2)

    with comm_check.recording() as rec:
        closed = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(1, 2)))(
            x, w1, w2)
    wheres = [w for w, _ in rec]
    assert "overlap.allgather_matmul" in wheres
    assert "overlap.matmul_reduce_scatter" in wheres
    assert all(s.axis == "mp" for _, s in rec)
    plan = StepPlan(
        mesh_axes={str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        nodes=[PlanNode("sp_pair", reads=("x",), writes=("loss",))],
        comm_specs=list(rec))
    diags = plan_check.check_plan(plan, closed)
    assert diags == [], [d.format() for d in diags]
    # and the recording is scoped: nothing recorded outside the context
    with comm_check.recording() as rec2:
        pass
    assert rec2 == []


# ---------------------------------------------------------------------------
# S-rules: seeded faults
# ---------------------------------------------------------------------------

def _sp_closed_and_specs():
    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(1, 1, 1, 1, n),
                ("pp", "dp", "sharding", "sep", "mp"))
    x = jnp.ones((2, 8 * n, 16), jnp.float32)
    w = jnp.ones((16, 32), jnp.float32)
    with comm_check.recording() as rec:
        closed = jax.make_jaxpr(
            lambda x, w: overlap.allgather_matmul(x, w, mesh=mesh,
                                                  chunks=1))(x, w)
    return mesh, closed, list(rec)


def test_s001_undeclared_collective_fires():
    mesh, closed, _rec = _sp_closed_and_specs()
    plan = StepPlan(
        mesh_axes={str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        nodes=[PlanNode("step")], comm_specs=[])  # declaration dropped
    diags = plan_check.check_plan(plan, closed)
    hits = [d for d in diags if d.rule == "S001"]
    assert hits and hits[0].severity == "error"
    assert "mp" in hits[0].message


def test_s002_phantom_commspec_fires():
    mesh, _closed, rec = _sp_closed_and_specs()
    plan = StepPlan(
        mesh_axes={str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        nodes=[PlanNode("step")], comm_specs=rec)
    # trace WITHOUT the decomposed loop: declaration has no evidence
    clean = jax.make_jaxpr(lambda a: a * 2)(jnp.ones((4,)))
    diags = plan_check.check_plan(plan, clean)
    assert "S002" in rules_of(diags)
    assert all(d.severity == "error" for d in diags if d.rule == "S002")


def test_s002_phantom_gather_declaration_fires():
    """Gather-ahead declared for a param the traced step never gathers."""
    ts, batch = _micro_ts("off", "tp_zero")
    closed, donate = ts.trace_step(batch)
    phantom = dict(ts.plan.gather.params)
    phantom["gpt.phantom.weight"] = P()
    ts.plan.params["gpt.phantom.weight"] = ParamInfo((512, 512), P("mp"))
    ts.plan.gather = dataclasses.replace(ts.plan.gather, params=phantom)
    diags = plan_check.check_plan(ts.plan, closed, donate_argnums=donate)
    hits = [d for d in diags if d.rule == "S002"]
    assert hits and "gpt.phantom.weight" in hits[0].message


def test_s003_undeclared_param_gather_fires():
    """An fsdp-sharded param gathered by a stray with_sharding_constraint
    outside the declared gather plan."""
    mesh = create_hybrid_mesh(sharding=jax.device_count())
    set_hybrid_mesh(mesh)
    w = jnp.ones((16, 8), jnp.float32)
    sharded_spec = P("sharding", None)
    gathered = NamedSharding(mesh, P())

    def step(w):
        wg = jax.lax.with_sharding_constraint(w, gathered)  # accidental
        return jnp.sum(wg ** 2)

    closed = jax.make_jaxpr(step)(w)
    plan = StepPlan(
        mesh_axes={str(a): int(mesh.shape[a]) for a in mesh.axis_names},
        fsdp_axis="sharding",
        params={"w": ParamInfo((16, 8), sharded_spec)},
        nodes=[PlanNode("step", reads=("params",), writes=("loss",))])
    diags = plan_check.check_plan(plan, closed)
    hits = [d for d in diags if d.rule == "S003"]
    assert hits and hits[0].severity == "error"
    # declared in a gather plan -> silence
    plan.gather = GatherPlan(depth=2, anchored=(True,), edges=(),
                             params={"w": P()})
    assert "S003" not in rules_of(plan_check.check_plan(plan, closed))


# ---------------------------------------------------------------------------
# D-rules: seeded faults
# ---------------------------------------------------------------------------

def _plan_with(nodes, **kw):
    return StepPlan(mesh_axes={"dp": 8}, nodes=list(nodes), **kw)


def test_d001_read_after_donation_fires():
    """The real accident shape: a donating compiled step composed with the
    offload streamer that still reads params per block."""
    plan = _plan_with([
        PlanNode("train_step", reads=("params", "batch"),
                 writes=("loss", "grads"), donates=("params",)),
        PlanNode("offload.update[0]", reads=("params[0]",),
                 writes=("params[0]",)),
    ])
    diags = plan_check.check_plan(plan)
    hits = [d for d in diags if d.rule == "D001"]
    assert hits and hits[0].severity == "error"
    assert "offload.update[0]" in hits[0].message


def test_d001_rewrite_revives_buffer():
    plan = _plan_with([
        PlanNode("a", donates=("x",), writes=("x",)),  # in-place update
        PlanNode("b", reads=("x",)),
    ])
    assert plan_check.check_plan(plan) == []


def test_d002_double_donation_fires():
    """Offload and the compiled step both claiming a buffer's lifetime."""
    plan = _plan_with([
        PlanNode("grad_step", reads=("params",), writes=("grads",),
                 donates=("moments",)),
        PlanNode("offload.update[0]", donates=("moments[0]",),
                 writes=("moments[0]",)),
    ])
    diags = plan_check.check_plan(plan)
    hits = [d for d in diags if d.rule == "D002"]
    assert hits and hits[0].severity == "error"


def test_d003_missing_edge_fires():
    ts, batch = _micro_ts("off", "tp_zero")
    closed, donate = ts.trace_step(batch)
    g = ts.plan.gather
    assert g.edges, "micro model must produce at least one barrier edge"
    ts.plan.gather = dataclasses.replace(g, edges=g.edges[:-1])
    diags = plan_check.check_plan(ts.plan, closed, donate_argnums=donate)
    hits = [d for d in diags if d.rule == "D003"]
    assert hits and "not total" in hits[0].message


def test_d003_backward_edge_fires():
    g = GatherPlan(depth=1, anchored=(True, True), edges=((1, 0), (0, 1)),
                   params={})
    plan = _plan_with([PlanNode("step")], gather=g)
    diags = plan_check.check_plan(plan)
    assert any(d.rule == "D003" and "cyclic" in d.message for d in diags)


def test_d003_declared_but_untraced_chain_fires():
    """Edges declared, but the traced graph has no optimization_barrier —
    the chain is a promise the program does not keep."""
    g = GatherPlan(depth=1, anchored=(True, True), edges=((0, 1),),
                   params={})
    plan = _plan_with([PlanNode("step")], gather=g)
    closed = jax.make_jaxpr(lambda a: a * 2)(jnp.ones((4,)))
    diags = plan_check.check_plan(plan, closed)
    assert any(d.rule == "D003" and "no optimization_barrier" in d.message
               for d in diags)


def test_d004_capacity_exceeded_fires():
    import tools.hbm_budget as hbm_budget
    # full-depth resident Adam: the exact wall the offload tier removes
    cap = hbm_budget.gpt_plan(layers=24, offload="off", batch=1)
    assert not cap["fits"]
    diags = plan_check.check_capacity(cap, where="test")
    assert [d.rule for d in diags] == ["D004"]
    plan = _plan_with([PlanNode("step")], capacity=cap)
    assert "D004" in rules_of(plan_check.check_plan(plan))
    # the offloaded composition fits -> silence
    ok = hbm_budget.tier_plan(offload="moments", remat=True)
    assert ok["fits"] and plan_check.check_capacity(ok) == []


# ---------------------------------------------------------------------------
# The barrier chain the real gather-ahead emits matches its declaration
# ---------------------------------------------------------------------------

def test_gather_ahead_plan_matches_traced_barriers():
    ts, batch = _micro_ts("off", "tp_zero")
    closed, _ = ts.trace_step(batch)
    facts = plan_check.collect_jaxpr_facts(closed)
    assert ts.plan.gather.edges, "depth-2 chain over 3 blocks: 1+ edges"
    assert facts.barriers >= len(ts.plan.gather.edges)


# ---------------------------------------------------------------------------
# comm_check helpers grown for the matrix
# ---------------------------------------------------------------------------

def test_spec_for_cp_ring_clean_at_long_context():
    spec = comm_check.spec_for_cp_ring(
        b=1, s_local=8192, heads=16, head_dim=128, n=4, itemsize=2)
    assert spec.axis == "sep" and spec.hops == 3 and spec.directions == 1
    assert comm_check.check_comm_spec(spec) == []


def test_spec_for_cp_ring_latency_floor_fires():
    spec = comm_check.spec_for_cp_ring(
        b=1, s_local=32, heads=2, head_dim=16, n=4, itemsize=2)
    assert "C002" in rules_of(comm_check.check_comm_spec(spec))


# ---------------------------------------------------------------------------
# The matrix driver (subset in-process; the full sweep is the CLI gate)
# ---------------------------------------------------------------------------

def test_matrix_subset_in_process(capsys):
    from tools import lint_graph
    combos = [
        {"offload_optimizer": "off", "comm_overlap": "off",
         "cp_nested_ring": False, "pallas_conv": 0, "remat": False},
        {"offload_optimizer": "moments", "comm_overlap": "tp_zero",
         "cp_nested_ring": True, "pallas_conv": 1, "remat": True},
    ]
    rc = lint_graph.run_matrix(with_dryrun=False, combos=combos,
                               min_severity="error")
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "2 combination(s), 0 error(s)" in out


def test_matrix_json_subset(capsys):
    import json
    from tools import lint_graph
    combos = [{"offload_optimizer": "off", "comm_overlap": "off",
               "cp_nested_ring": False, "pallas_conv": 0, "remat": False}]
    rc = lint_graph.run_matrix(json_mode=True, with_dryrun=False,
                               combos=combos)
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["errors"] == 0 and len(report["combos"]) == 1
    entry = report["combos"][0]
    assert entry["flags"]["comm_overlap"] == "off"
    assert entry["hbm"]["fits"] is True


def test_tier_combo_enumeration_is_complete():
    combos = list(plan_check.iter_tier_combos())
    # offload x comm_overlap x multislice x cp_ring x pallas_conv x remat
    assert len(combos) == 2 * 4 * 2 * 2 * 2 * 2
    assert len({tuple(sorted(c.items())) for c in combos}) == len(combos)
    assert {c["multislice"] for c in combos} == {"off", "hierarchical"}
