"""Step-compiler pass pipeline tests (framework/step_pipeline.py +
analysis/pass_check.py): every tier combo composes clean through the
G-rules, the composed-plan hash is deterministic across process
restarts and invariant under declared-commutative swaps, G001/G002/G004
each fire on seeded bad orderings, the pipeline's step outputs are
bitwise-identical to a hand-spliced legacy reference (plain, sentinel,
offload), and the previously hand-rejected compositions —
sentinel x offload, offload + tp_zero + pp — compose legally with
loss/update parity and zero G/plan errors on the CPU mesh."""

import dataclasses
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import pass_check, plan_check
from paddle_tpu.analysis.pass_check import PassContract
from paddle_tpu.core import flags
from paddle_tpu.framework import step_pipeline as sp


def _all_combo_hashes():
    out = {}
    for i, combo in enumerate(plan_check.iter_tier_combos()):
        for sentinel in (False, True):
            b = sp.compose(sp.plan_only_build(combo,
                                              health_sentinel=sentinel))
            errs = [d for d in b.diagnostics if d.severity == "error"]
            assert not errs, (combo, sentinel,
                              [d.format() for d in errs])
            out[f"{i}:{int(sentinel)}"] = \
                pass_check.composed_plan_hash(b.plan)
    return out


# ---------------------------------------------------------------------------
# Property: every combo composes clean; hashes deterministic + commutative
# ---------------------------------------------------------------------------

def test_all_combos_compose_clean_through_g_rules():
    hashes = _all_combo_hashes()
    assert len(hashes) == 2 * len(list(plan_check.iter_tier_combos()))
    # distinct plan shapes exist (offload/comm/remat/sentinel all bite)
    assert len(set(hashes.values())) >= 16


def test_composed_plan_hash_deterministic_across_process_restart():
    """The hash must key a cross-run CI diff and the matrix trace cache:
    recompute every combo's hash in a fresh interpreter and compare."""
    code = (
        "import sys; sys.path.insert(0, {repo!r})\n"
        "import tests.test_step_pipeline as t, json\n"
        "print(json.dumps(t._all_combo_hashes()))\n"
    ).format(repo=str(__import__("pathlib").Path(__file__).parents[1]))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    fresh = json.loads(proc.stdout.strip().splitlines()[-1])
    assert fresh == _all_combo_hashes()


def test_hash_invariant_under_declared_commutative_swaps():
    """Adjacent active passes with NO declared ordering edge must
    commute in plan space — rebuilding with the pair swapped yields the
    identical composed-plan hash (the property G004 enforces; here it is
    asserted directly on the busiest combos)."""
    busy = [
        dict(offload_optimizer="moments", comm_overlap="all",
             multislice="off", cp_nested_ring=False, pallas_conv=0,
             remat=True),
        dict(offload_optimizer="off", comm_overlap="tp_zero",
             multislice="hierarchical", cp_nested_ring=False,
             pallas_conv=0, remat=True),
    ]
    by_name = {p.contract.name: p for p in sp.PIPELINE}
    n_swaps = 0
    for combo in busy:
        for sentinel in (False, True):
            base = sp.compose(sp.plan_only_build(
                combo, health_sentinel=sentinel), check=False)
            base_hash = pass_check.composed_plan_hash(base.plan)
            names = [c.name for c in base.contracts]
            for i in range(len(names) - 1):
                a = by_name[names[i]].contract
                b = by_name[names[i + 1]].contract
                if pass_check._declared_edge(a, b):
                    continue
                swapped = list(names)
                swapped[i], swapped[i + 1] = swapped[i + 1], swapped[i]
                rb = sp.compose(
                    sp.plan_only_build(combo, health_sentinel=sentinel),
                    order=[by_name[n] for n in swapped], check=False)
                assert pass_check.composed_plan_hash(rb.plan) == \
                    base_hash, (combo, names[i], names[i + 1])
                n_swaps += 1
    assert n_swaps >= 4  # the property actually exercised something


# ---------------------------------------------------------------------------
# Seeded bad orderings: G001 / G002 / G004 must fire
# ---------------------------------------------------------------------------

_COMBO = dict(offload_optimizer="moments", comm_overlap="tp_zero",
              multislice="off", cp_nested_ring=False, pallas_conv=0,
              remat=False)
_PIPE = {p.contract.name: p for p in sp.PIPELINE}


def test_g001_fires_on_pass_before_its_provider():
    b = sp.plan_only_build(_COMBO)
    sp.compose(b, order=[_PIPE["offload_stream"], _PIPE["base_grad"]])
    fired = [d for d in b.diagnostics if d.rule == "G001"]
    assert fired and all(d.severity == "error" for d in fired)
    # structurally-bad composition stops before plan emission
    assert b.plan is None


def test_g002_fires_on_conflicting_ownership_without_handoff():
    class Rogue(sp.StepPass):
        contract = PassContract(
            name="rogue", requires=("grads",), provides=("rogue",),
            terminal=("rogue",), plan_writes=("params",),
            plan_donates=("params",))

    b = sp.plan_only_build(_COMBO)
    sp.compose(b, order=[_PIPE["base_grad"], Rogue(),
                         _PIPE["offload_stream"]])
    assert any(d.rule == "G002" for d in b.diagnostics)


def test_g003_fires_on_undeclared_plan_delta():
    class Sneaky(sp.StepPass):
        contract = PassContract(name="sneaky", requires=("loss",),
                                provides=("sneak",), terminal=("sneak",))

        def plan_apply(self, build):
            build.plan.nodes.append(plan_check.PlanNode(
                "sneak_node", reads=("params",), writes=("params",)))

    b = sp.plan_only_build(_COMBO)
    sp.compose(b, order=[_PIPE["base_grad"], Sneaky(),
                         _PIPE["offload_stream"]])
    assert any(d.rule == "G003" for d in b.diagnostics)


def test_g004_fires_when_order_sensitive_pair_loses_its_edge():
    class NoEdgeSentinel(sp.HealthSentinelPass):
        contract = dataclasses.replace(
            sp.HealthSentinelPass.contract, order_after=())

    b = sp.plan_only_build(_COMBO, health_sentinel=True)
    order = [NoEdgeSentinel() if isinstance(p, sp.HealthSentinelPass)
             else p for p in sp.PIPELINE]
    sp.compose(b, order=order)
    assert any(d.rule == "G004" for d in b.diagnostics)
    # with the edge declared (the shipped contract), G004 is silent
    b2 = sp.compose(sp.plan_only_build(_COMBO, health_sentinel=True))
    assert not [d for d in b2.diagnostics if d.rule == "G004"]


def test_g005_warns_on_orphan_capability():
    class Orphan(sp.StepPass):
        contract = PassContract(name="orphan", requires=("loss",),
                                provides=("nobody_wants_this",))

    b = sp.plan_only_build(dict(_COMBO, offload_optimizer="off"))
    sp.compose(b, order=[_PIPE["base_grad"], Orphan()])
    fired = [d for d in b.diagnostics if d.rule == "G005"]
    assert fired and all(d.severity == "warning" for d in fired)


# ---------------------------------------------------------------------------
# Combo normalization (the one entry point; legacy 5-flag dicts warn once)
# ---------------------------------------------------------------------------

def test_normalize_combo_warns_once_on_legacy_shape_and_fills_default():
    plan_check._legacy_combo_warned = False
    legacy = {"offload_optimizer": "off", "comm_overlap": "tp",
              "cp_nested_ring": False, "pallas_conv": 0, "remat": False}
    with pytest.warns(UserWarning, match="legacy tier-flag combo"):
        full = plan_check.normalize_combo(legacy)
    assert full["multislice"] == "off"
    assert set(full) == {n for n, _ in plan_check.TIER_FLAGS}
    # warn-ONCE: the second legacy dict passes silently
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = plan_check.normalize_combo(dict(legacy))
    assert again == full


def test_normalize_combo_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown tier-flag key"):
        plan_check.normalize_combo({"offload_optimizer": "off",
                                    "not_a_tier_flag": 1})


def test_plan_only_build_accepts_legacy_combo_via_normalize():
    plan_check._legacy_combo_warned = True  # already warned this process
    b = sp.plan_only_build({"offload_optimizer": "off",
                            "comm_overlap": "off",
                            "cp_nested_ring": False, "pallas_conv": 0,
                            "remat": False})
    assert b.flags["multislice"] == "off"


# ---------------------------------------------------------------------------
# Bitwise parity vs the hand-spliced legacy step (plain/sentinel/offload)
# ---------------------------------------------------------------------------

def _mlp_and_data(n_steps=3):
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.nn import functional as F

    paddle.seed(11)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))

    def loss_fn(model, params, batch):
        x, y = batch
        return F.cross_entropy(functional_call(model, params, x), y).mean()

    rng = np.random.default_rng(7)
    batches = [(rng.standard_normal((8, 8)).astype("float32"),
                rng.integers(0, 4, size=(8,)).astype("int32"))
               for _ in range(n_steps)]
    return net, loss_fn, batches


def _legacy_spliced_run(kind, batches):
    """The pre-pipeline TrainStep splicing, reconstructed by hand: the
    exact closures the legacy __init__ built for the plain / sentinel /
    offload branches, jitted and dispatched the same way. The pipeline
    must reproduce its outputs BITWISE."""
    from paddle_tpu.core.random import rng_scope
    from paddle_tpu.fault import health as _health
    from paddle_tpu.framework import offload as _offload
    from paddle_tpu.framework.functional import get_params
    from paddle_tpu.optimizer import Adam

    net, loss_fn, _ = _mlp_and_data()
    params = {n: jnp.copy(v)
              for n, v in get_params(net, trainable_only=True).items()}
    optimizer = Adam(1e-2)
    opt_state = optimizer.init(params)
    base_key = jax.random.key(0)
    lr = jnp.asarray(optimizer.get_lr(), jnp.float32)

    def compute_grads(p, batch, key):
        def loss_of(pp):
            with rng_scope(key):
                return loss_fn(net, pp, batch), {}

        (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(p)
        return loss, grads

    losses = []
    if kind == "plain":
        @jax.jit
        def step(p, st, batch, l, key):
            loss, grads = compute_grads(p, batch, key)
            _health.check_numerics(loss=loss, grads=grads,
                                   where="train_step")
            np_, ns = optimizer.apply_gradients(p, grads, st, l)
            _health.check_numerics(opt_state=ns, where="train_step")
            return loss, np_, ns

        for i, b in enumerate(batches):
            key = jax.random.fold_in(base_key, i + 1)
            loss, params, opt_state = step(params, opt_state, b, lr, key)
            losses.append(loss)
    elif kind == "sentinel":
        sentinel = _health.StepSentinel()

        @jax.jit
        def step(p, st, batch, l, key, guard):
            loss, grads = compute_grads(p, batch, key)
            _health.check_numerics(loss=loss, grads=grads,
                                   where="train_step")
            stats = _health.fused_stats(loss, grads)
            ok = _health.fused_ok(stats, guard)
            np_, ns = optimizer.apply_gradients(p, grads, st, l)
            _health.check_numerics(opt_state=ns, where="train_step")
            keep = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            np_ = jax.tree_util.tree_map(keep, np_, p)
            ns = jax.tree_util.tree_map(keep, ns, st)
            stats = jnp.concatenate([stats, ok.astype(jnp.float32)[None]])
            return loss, stats, np_, ns

        for i, b in enumerate(batches):
            key = jax.random.fold_in(base_key, i + 1)
            guard = jnp.asarray(sentinel.guard_vector())
            loss, stats, params, opt_state = step(params, opt_state, b,
                                                  lr, key, guard)
            sentinel.verdict(stats)
            losses.append(loss)
    elif kind == "offload":
        su = _offload.StreamingUpdate(optimizer)
        opt_state = su.place(opt_state)

        @jax.jit
        def gstep(p, batch, key):
            loss, grads = compute_grads(p, batch, key)
            _health.check_numerics(loss=loss, grads=grads,
                                   where="train_step")
            return loss, grads

        for i, b in enumerate(batches):
            key = jax.random.fold_in(base_key, i + 1)
            loss, grads = gstep(params, b, key)
            params, opt_state = su.update(params, grads, opt_state, lr)
            losses.append(loss)
    return [np.asarray(v) for v in losses], \
        jax.tree_util.tree_map(np.asarray, params)


def _pipeline_run(kind, batches):
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import Adam

    net, loss_fn, _ = _mlp_and_data()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    set = {}
    if kind == "sentinel":
        set = {"health_sentinel": "on"}
    elif kind == "offload":
        set = {"offload_optimizer": "moments"}
    flags.set_flags(set)
    try:
        ts = make_sharded_train_step(net, Adam(1e-2), loss_fn, mesh=mesh,
                                     fsdp_axis=None)
        assert not [d for d in ts._pass_diags if d.severity == "error"]
        losses = [np.asarray(ts.step(b)) for b in batches]
    finally:
        flags.set_flags({"health_sentinel": "off",
                         "offload_optimizer": "off"})
    return losses, jax.tree_util.tree_map(np.asarray, ts.params), ts


@pytest.mark.parametrize("kind", ["plain", "sentinel", "offload"])
def test_pipeline_bitwise_parity_with_legacy_spliced_step(kind):
    if kind == "offload":
        from paddle_tpu.framework import offload
        if offload.host_memory_kind() is None:
            pytest.skip("no host memory tier on this runtime")
    _, _, batches = _mlp_and_data()
    ref_losses, ref_params = _legacy_spliced_run(kind, batches)
    got_losses, got_params, ts = _pipeline_run(kind, batches)
    expect_kind = {"plain": "plain", "sentinel": "sentinel",
                   "offload": "offload"}[kind]
    assert ts._step_kind == expect_kind
    for i, (a, b) in enumerate(zip(ref_losses, got_losses)):
        assert a.tobytes() == b.tobytes(), f"loss diverged at step {i}"
    for name in ref_params:
        assert ref_params[name].tobytes() == got_params[name].tobytes(), \
            name


# ---------------------------------------------------------------------------
# Previously hand-rejected: offload + tp_zero + pp composes and matches
# ---------------------------------------------------------------------------

def _pp_step(offload_on):
    from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                                 set_hybrid_mesh)
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.framework.sharded import make_sharded_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=32,
                    hidden_dropout=0.0, attention_dropout=0.0,
                    use_flash_attention=False)

    def loss_fn(m, p, b):
        ids, labels = b
        return functional_call(m, p, ids, labels, training=True)

    flags.set_flags({
        "offload_optimizer": "moments" if offload_on else "off",
        "comm_overlap": "tp_zero"})
    mesh = create_hybrid_mesh(pp=2, dp=2, sharding=2)
    set_hybrid_mesh(mesh)
    ts = make_sharded_train_step(GPTForCausalLM(cfg), AdamW(1e-3),
                                 loss_fn, mesh=mesh)
    ids = np.zeros((4, 16), np.int64)
    ids = np.arange(64, dtype=np.int64).reshape(4, 16) % 64
    return ts, (ids.astype(np.int32), ids.astype(np.int32))


def test_offload_tp_zero_pp_composes_with_parity():
    """The second previously-rejected composition: optimizer-moment
    streaming + ZeRO-3 gather-ahead on a pp=2 x dp=2 x sharding=2 mesh.
    Must compose with zero G errors, verify clean through the S/D plan
    rules against its trace, and match the unoffloaded arm's losses and
    updated params."""
    from paddle_tpu.framework import offload
    if offload.host_memory_kind() is None:
        pytest.skip("no host memory tier on this runtime")
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    try:
        ts_ref, batch = _pp_step(offload_on=False)
        ref = [float(ts_ref.step(batch)) for _ in range(2)]
        ref_params = jax.tree_util.tree_map(np.asarray, ts_ref.params)

        ts, batch = _pp_step(offload_on=True)
        assert ts._step_kind == "offload"
        assert ts._gather_specs  # gather-ahead really active
        order = [c.name for c in ts._pass_contracts]
        assert order[:4] == ["base_grad", "sp_decompose",
                             "zero_gather_ahead", "offload_stream"]
        assert set(order[4:]) <= {"telemetry"}
        assert not [d for d in ts._pass_diags if d.severity == "error"]
        got = [float(ts.step(batch)) for _ in range(2)]
        np.testing.assert_allclose(got, ref, rtol=1e-6)
        got_params = jax.tree_util.tree_map(np.asarray, ts.params)
        for name in ref_params:
            np.testing.assert_allclose(
                got_params[name], ref_params[name], rtol=1e-5,
                atol=1e-7, err_msg=name)

        # zero plan errors on the real trace (S/D rules)
        closed, donate = ts.trace_step(batch)
        pd = plan_check.check_plan(ts.plan, closed, donate_argnums=donate,
                                   where="test.pp")
        assert not [d for d in pd if d.severity == "error"], \
            [d.format() for d in pd]
        # the traced CommSpecs stay within the composed contracts
        cd = pass_check.check_traced_comm(
            ts._pass_contracts, ts.plan.comm_specs,
            ambient=sp.AMBIENT_COMM_SPECS)
        assert not cd, [d.format() for d in cd]
    finally:
        flags.set_flags({"offload_optimizer": "off",
                         "comm_overlap": "off"})


# ---------------------------------------------------------------------------
# Registry + report plumbing
# ---------------------------------------------------------------------------

def test_pass_rule_registry_and_report():
    rules = pass_check.all_pass_rules()
    assert [r.rule_id for r in rules] == \
        ["G001", "G002", "G003", "G004", "G005"]
    b = sp.compose(sp.plan_only_build(dict(_COMBO)))
    rep = sp.pipeline_report(b)
    assert rep["order"] == [c.name for c in b.contracts]
    assert set(rep["contracts"]) == set(rep["order"])
    assert len(rep["plan_hash"]) == 64
    json.dumps(rep)  # serializable as-is (the lint_graph --json slice)


def test_contract_hash_stable_and_shape_sensitive():
    c = sp.BaseGradPass.contract
    assert pass_check.contract_hash(c) == pass_check.contract_hash(
        dataclasses.replace(c))
    assert pass_check.contract_hash(c) != pass_check.contract_hash(
        dataclasses.replace(c, provides=c.provides + ("x",)))
