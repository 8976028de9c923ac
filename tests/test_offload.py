"""Optimizer-state offload parity suite (ISSUE r6 tentpole) on the CPU
mesh, where the host memory kind is ``unpinned_host`` (the CPU default) —
the placement/streaming/donation machinery runs for real, with host and
device tiers sharing silicon, so every comparison can demand bitwise
equality with the resident path.

Covers the four acceptance rows: (1) offloaded Adam ==(bitwise) resident
Adam over N steps, (2) donation never aliases the caller's live host
moments, (3) checkpoint save/resume round-trips host-placed state, (4)
``FLAGS_offload_optimizer=off`` is byte-identical to the pre-offload
path (same code path, moments stay in default device memory)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import flags as core_flags
from paddle_tpu.framework import offload
from paddle_tpu.framework.functional import functional_call, get_params
from paddle_tpu.optimizer import SGD, Adam, AdamW, Momentum

warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@pytest.fixture
def offload_flag():
    core_flags.set_flags({"offload_optimizer": "moments"})
    yield
    core_flags.set_flags({"offload_optimizer": "off"})


def _mlp(seed=0, bf16=True):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    if bf16:
        m.astype(paddle.bfloat16)
    return m


def _data(n=4, seed=0, dtype=jnp.bfloat16, batch=4):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.standard_normal((batch, 8)), dtype),
             jnp.asarray(rng.standard_normal((batch, 4)), dtype))
            for _ in range(n)]


def _loss_of(model):
    def loss(p, x, y):
        out = functional_call(model, p, x, training=True)
        return jnp.mean((out.astype(jnp.float32) -
                         y.astype(jnp.float32)) ** 2)
    return loss


def _run_resident(model, opt, params, data):
    grad_fn = jax.jit(jax.value_and_grad(_loss_of(model)))
    apply_jit = jax.jit(opt.apply_gradients)
    st, p = opt.init(params), dict(params)
    for x, y in data:
        _, g = grad_fn(p, x, y)
        p, st = apply_jit(p, g, st, jnp.float32(1e-2))
    return p, st


def _run_streamed(model, opt, params, data):
    su = offload.StreamingUpdate(opt)
    grad_fn = jax.jit(jax.value_and_grad(_loss_of(model)))
    st, p = su.init_state(params), dict(params)
    for x, y in data:
        _, g = grad_fn(p, x, y)
        p, st = su.update(p, g, st, jnp.float32(1e-2))
    return p, st, su


# ---------------------------------------------------------------------------
# Grouping
# ---------------------------------------------------------------------------

def test_block_grouping_order():
    names = ["gpt.h.10.w", "gpt.h.2.w", "gpt.wte", "gpt.h.2.b", "gpt.ln_f"]
    groups = offload.group_by_block(names)
    assert groups[0] == (("", -1), ["gpt.wte", "gpt.ln_f"])
    assert groups[1] == (("gpt.h", 2), ["gpt.h.2.w", "gpt.h.2.b"])
    assert groups[2] == (("gpt.h", 10), ["gpt.h.10.w"])


def test_offloadable_keys_per_optimizer():
    assert set(Adam().offloadable_state_keys()) == {"moment1", "moment2"}
    assert set(AdamW().offloadable_state_keys()) == {"moment1", "moment2"}
    assert set(Momentum().offloadable_state_keys()) == {"velocity"}
    assert SGD().offloadable_state_keys() == ()


# ---------------------------------------------------------------------------
# (1) parity: streamed == resident, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_cls", [AdamW, Adam, Momentum])
def test_streamed_matches_resident_bitwise(opt_cls):
    model = _mlp()
    params = get_params(model)
    data = _data(5)
    p_res, st_res = _run_resident(
        model, opt_cls(learning_rate=1e-2, multi_precision=True), params,
        data)
    p_str, st_str, su = _run_streamed(
        model, opt_cls(learning_rate=1e-2, multi_precision=True), params,
        data)
    for n in p_res:
        np.testing.assert_array_equal(
            np.asarray(p_res[n], np.float32), np.asarray(p_str[n],
                                                         np.float32), n)
    assert int(st_res["step"]) == int(st_str["step"]) == len(data)
    for n, st in st_res["param_states"].items():
        for k, v in st.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(st_str["param_states"][n][k]),
                f"{n}@{k}")
            if k in su._moment_keys:
                got = st_str["param_states"][n][k].sharding.memory_kind
                assert got == su.host_kind, f"{n}@{k} not host-committed"


def test_global_norm_clip_applied_once_not_per_block():
    """Global-norm clip must see the WHOLE gradient tree; the streaming
    path clips before splitting into blocks — results must match the
    resident path bitwise (a per-block clip would compute block-local
    norms and diverge)."""
    model = _mlp()
    params = get_params(model)
    data = _data(3)
    mk = lambda: AdamW(learning_rate=1e-2, multi_precision=True,
                       grad_clip=nn.ClipGradByGlobalNorm(1e-3))
    p_res, _ = _run_resident(model, mk(), params, data)
    p_str, _, _ = _run_streamed(model, mk(), params, data)
    for n in p_res:
        np.testing.assert_array_equal(
            np.asarray(p_res[n], np.float32),
            np.asarray(p_str[n], np.float32), n)


def test_sgd_no_moment_zero_transfer():
    """SGD(multi_precision) is the resident fast path: nothing to
    offload, update bitwise-identical whether 'streamed' or not."""
    model = _mlp()
    params = get_params(model)
    data = _data(3)
    p_res, st_res = _run_resident(
        model, SGD(learning_rate=1e-2, multi_precision=True), params, data)
    p_str, st_str, _ = _run_streamed(
        model, SGD(learning_rate=1e-2, multi_precision=True), params, data)
    for n in p_res:
        np.testing.assert_array_equal(np.asarray(p_res[n], np.float32),
                                      np.asarray(p_str[n], np.float32))
    for n, st in st_str["param_states"].items():
        assert set(st) <= {"master"}  # no moment leaves at all


# ---------------------------------------------------------------------------
# (2) donation must not alias live moments
# ---------------------------------------------------------------------------

def test_donation_does_not_alias_live_moments():
    model = _mlp()
    params = get_params(model)
    opt = AdamW(learning_rate=1e-2, multi_precision=True)
    su = offload.StreamingUpdate(opt)
    st = su.init_state(params)
    grad_fn = jax.jit(jax.value_and_grad(_loss_of(model)))
    x, y = _data(1)[0]
    _, g = grad_fn(params, x, y)
    # run one update to get non-zero moments, then hold references
    p1, st1 = su.update(params, g, st, jnp.float32(1e-2))
    held = {n: {k: (v, np.asarray(v))
                for k, v in s.items() if k in su._moment_keys}
            for n, s in st1["param_states"].items()}
    _, g1 = grad_fn(p1, x, y)
    p2, st2 = su.update(p1, g1, st1, jnp.float32(1e-2))
    jax.block_until_ready(jax.tree_util.tree_leaves(st2))
    for n, kv in held.items():
        for k, (arr, before) in kv.items():
            # the held (pre-update) host arrays are still alive and
            # unchanged — the update donated only its in-flight copies
            assert not arr.is_deleted(), f"{n}@{k} was donated away"
            np.testing.assert_array_equal(np.asarray(arr), before,
                                          f"{n}@{k} mutated in place")
            # and the update really produced different moments
    changed = any(
        not np.array_equal(np.asarray(st2["param_states"][n][k]),
                           before)
        for n, kv in held.items() for k, (_, before) in kv.items())
    assert changed


# ---------------------------------------------------------------------------
# (3) checkpoint round-trip of host-placed state
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_host_state(tmp_path):
    """Training N+M steps straight must equal train N -> save (params +
    host-placed opt state) -> load -> re-place -> train M."""
    from paddle_tpu.framework import io as fio

    data = _data(6)

    def fresh():
        model = _mlp(seed=3)
        opt = AdamW(learning_rate=1e-2, multi_precision=True)
        return model, opt, get_params(model)

    model, opt, params = fresh()
    p_straight, _, _ = _run_streamed(model, opt, params, data)

    model, opt, params = fresh()
    su = offload.StreamingUpdate(opt)
    grad_fn = jax.jit(jax.value_and_grad(_loss_of(model)))
    st, p = su.init_state(params), dict(params)
    for x, y in data[:3]:
        _, g = grad_fn(p, x, y)
        p, st = su.update(p, g, st, jnp.float32(1e-2))
    fio.save({"params": p, "opt": st}, str(tmp_path / "state.pdparams"))

    loaded = fio.load(str(tmp_path / "state.pdparams"))
    lp = {k: jnp.asarray(v).astype(jnp.bfloat16)
          for k, v in loaded["params"].items()}
    # loaded arrays land in default memory; place() re-homes the moments
    st2 = su.place(loaded["opt"])
    for n, s in st2["param_states"].items():
        for k, v in s.items():
            if k in su._moment_keys:
                assert v.sharding.memory_kind == su.host_kind
    for x, y in data[3:]:
        _, g = grad_fn(lp, x, y)
        lp, st2 = su.update(lp, g, st2, jnp.float32(1e-2))
    for n in p_straight:
        np.testing.assert_array_equal(
            np.asarray(p_straight[n], np.float32),
            np.asarray(lp[n], np.float32), n)


# ---------------------------------------------------------------------------
# (4) flag wiring through sharded.TrainStep
# ---------------------------------------------------------------------------

def _train_step_losses(n_steps=3):
    from paddle_tpu.framework.sharded import make_sharded_train_step

    model = _mlp(seed=1, bf16=False)

    def loss_fn(model, params, batch):
        x, y = batch
        out = functional_call(model, params, x, training=True)
        return jnp.mean((out - y) ** 2)

    ts = make_sharded_train_step(model, AdamW(learning_rate=1e-2), loss_fn)
    # batch divisible by the 8-device default dp mesh
    data = _data(n_steps, dtype=jnp.float32, batch=8)
    return [float(ts.step(b)) for b in data], ts


def test_trainstep_flag_off_is_todays_path():
    losses, ts = _train_step_losses()
    assert ts._offload is None
    host = offload.host_memory_kind()
    dev_kind = jax.devices()[0].default_memory().kind
    for st in ts.opt_state["param_states"].values():
        for k, v in st.items():
            assert v.sharding.memory_kind == dev_kind
    assert all(np.isfinite(losses))


def test_trainstep_flag_moments_matches_off_bitwise(offload_flag):
    losses_on, ts_on = _train_step_losses()
    assert ts_on._offload is not None
    core_flags.set_flags({"offload_optimizer": "off"})
    losses_off, ts_off = _train_step_losses()
    np.testing.assert_array_equal(losses_on, losses_off)
    for n in ts_on.params:
        np.testing.assert_array_equal(np.asarray(ts_on.params[n]),
                                      np.asarray(ts_off.params[n]), n)
    su = ts_on._offload
    for n, st in ts_on.opt_state["param_states"].items():
        for k, v in st.items():
            if k in su._moment_keys:
                assert v.sharding.memory_kind == su.host_kind


# ---------------------------------------------------------------------------
# capacity plan + hbm_budget tool
# ---------------------------------------------------------------------------

def test_capacity_plan_accounts_host_side():
    # >=3 blocks so moments_in_flight (top-2 blocks) < total moments
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 16),
                          nn.Tanh(), nn.Linear(16, 4))
    model.astype(paddle.bfloat16)
    params = get_params(model)
    opt = AdamW(multi_precision=True)
    res = offload.capacity_plan(params, opt, mode="off")
    off = offload.capacity_plan(params, opt, mode="moments")
    assert res.rows["moments"] == off.rows["host_moments"]
    assert off.rows["moments_in_flight"] <= res.rows["moments"]
    assert off.device_bytes < res.device_bytes
    assert off.to_json()["mode"] == "moments"


def test_hbm_budget_known_depths():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    from tools import hbm_budget

    n, _, _ = hbm_budget.gpt_param_counts(24, 2048, 2048, 50304)
    assert n == 1315819520  # exact count of the built 1.3B model
    # L=12 resident Adam fits (the benchmark's training cell runs it); L=24
    # does not (the 18.4 GB wall); offloading the moments makes L=24 fit.
    assert hbm_budget.gpt_plan(layers=12)["fits"]
    assert not hbm_budget.gpt_plan(layers=24)["fits"]
    b, plan = hbm_budget.choose_batch(layers=24, optimizer="adamw",
                                      offload="moments")
    assert b is not None and plan["fits"]
    assert plan["rows_gb"]["moments_in_flight"] < 2.0
    b_sgd, plan_sgd = hbm_budget.choose_batch(layers=24, optimizer="sgd")
    assert b_sgd is not None and plan_sgd["fits"]
    assert hbm_budget.main(["--layers", "24"]) == 1
    assert hbm_budget.main(["--layers", "24", "--offload", "moments",
                            "--batch", "2"]) == 0


# ---------------------------------------------------------------------------
# Donation hygiene of the streaming block program (lint rule J009)
# ---------------------------------------------------------------------------

class TestStreamingDonationLint:

    def _block_args(self):
        from paddle_tpu.optimizer import AdamW
        model = _mlp(bf16=False)
        params = get_params(model)
        opt = AdamW(learning_rate=1e-3)
        su = offload.StreamingUpdate(opt)
        state = su.init_state(params)
        grads = {k: jnp.ones_like(v) for k, v in params.items()}
        names = offload.group_by_block(list(params))[0][1]
        p_blk = {n: params[n] for n in names}
        g_blk = {n: grads[n] for n in names}
        st_blk = {n: {k: jax.device_put(v, params[n].sharding)
                      for k, v in state["param_states"][n].items()}
                  for n in names}
        return su, (p_blk, g_blk, st_blk, state["step"], jnp.float32(1e-3))

    def test_j009_negative_on_streaming_block(self):
        """The real per-block update donates (params, grads, moments) and
        returns TRANSFORMED buffers — the donated-passthrough rule must
        stay silent on the path that donates the most."""
        from paddle_tpu.analysis import lint_fn
        su, args = self._block_args()
        diags = lint_fn(su._block_fn.__wrapped__, *args,
                        donate_argnums=(0, 1, 2), where="offload.block")
        assert "J009" not in {d.rule for d in diags}, \
            [d.format() for d in diags if d.rule == "J009"]

    def test_j009_positive_on_passthrough_block(self):
        """A broken block update that forwards a donated buffer unchanged
        (e.g. skipping a param's update) trips J009."""
        from paddle_tpu.analysis import lint_fn
        su, args = self._block_args()

        def bad_block(p_blk, g_blk, st_blk, step, lr):
            return p_blk, st_blk  # donated inputs flow straight out

        diags = lint_fn(bad_block, *args, donate_argnums=(0, 1, 2),
                        where="offload.block")
        hits = [d for d in diags if d.rule == "J009"]
        assert hits and hits[0].severity == "error"
