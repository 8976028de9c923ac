"""Pipeline-parallel schedule tests on the 8-device CPU mesh.

Parity model (SURVEY §4): pipeline output/training must match the sequential
single-device execution of the same layers — the analog of the reference's
hybrid_parallel_pp_model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import (
    LayerDesc, PipelineLayer)
from paddle_tpu.distributed.pipeline_schedule import (analyze_pipeline,
                                                      make_pipeline_train_step,
                                                      spmd_pipeline)
from paddle_tpu.framework.functional import get_params, set_params
from paddle_tpu.optimizer import AdamW


def test_spmd_pipeline_matches_sequential():
    S, n_micro, mb, d = 4, 8, 2, 16
    mesh = create_hybrid_mesh(pp=S, dp=2)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((S, d)) * 0.1, jnp.float32)
    x_mb = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage_fn(sp, x):
        return jnp.tanh(x @ sp["w"] + sp["b"])

    y = spmd_pipeline(stage_fn, {"w": w, "b": b}, x_mb, mesh)

    ref = x_mb
    for s in range(S):
        ref = jnp.tanh(ref @ w[s] + b[s])
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)


def test_spmd_pipeline_grads_match_sequential():
    S, n_micro, mb, d = 4, 4, 2, 8
    mesh = create_hybrid_mesh(pp=S, dp=2)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
    x_mb = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage_fn(sp, x):
        return jnp.tanh(x @ sp["w"])

    def loss_pipe(w):
        return jnp.mean(spmd_pipeline(stage_fn, {"w": w}, x_mb, mesh) ** 2)

    def loss_seq(w):
        y = x_mb
        for s in range(S):
            y = jnp.tanh(y @ w[s])
        return jnp.mean(y ** 2)

    gp = jax.grad(loss_pipe)(w)
    gs = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(gp, gs, rtol=1e-4, atol=1e-6)


def _make_pl(n_blocks=8, d=16, seed=0):
    paddle.seed(seed)
    descs = [LayerDesc(nn.Linear, d, d) for _ in range(n_blocks)]

    def loss_fn(out, labels):
        return jnp.mean((out - labels) ** 2)

    return PipelineLayer(layers=descs, num_stages=4, loss_fn=loss_fn)


def test_analyze_homogeneous():
    pl = _make_pl()
    a = analyze_pipeline(pl, 4)
    assert a.homogeneous
    assert len(a.pre) == 0 and len(a.post) == 0
    assert all(len(c) == 2 for c in a.cores)


class _Embed(nn.Layer):
    def __init__(self, d):
        super().__init__()
        self.fc = nn.Linear(d, d)

    def forward(self, x):
        return self.fc(x) * 2.0


def test_analyze_with_pre_post():
    paddle.seed(0)
    d = 8
    descs = ([LayerDesc(_Embed, d)] +
             [LayerDesc(nn.Linear, d, d) for _ in range(8)] +
             [LayerDesc(nn.LayerNorm, d)])
    pl = PipelineLayer(layers=descs, num_stages=4,
                       loss_fn=lambda o, l: jnp.mean((o - l) ** 2))
    # Stage segments are uniform over 10 layers → [3,2,2,3]: pre=_Embed,
    # post=LayerNorm, cores of 2 Linears each.
    a = analyze_pipeline(pl, 4)
    assert a.homogeneous
    assert len(a.pre) == 1 and type(a.pre[0][1]).__name__ == "_Embed"
    assert len(a.post) == 1 and type(a.post[0][1]).__name__ == "LayerNorm"


def _train(pl, mesh_kwargs, n_micro, steps=3, seed=0):
    mesh = create_hybrid_mesh(**mesh_kwargs)
    set_hybrid_mesh(mesh)
    opt = AdamW(learning_rate=1e-2)
    step = make_pipeline_train_step(pl, opt, n_microbatch=n_micro)
    params = get_params(pl)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    losses = []
    for i in range(steps):
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        params, opt_state, loss = step(params, opt_state, x, y,
                                       jnp.float32(1e-2))
        losses.append(float(loss))
    return losses


def test_pipeline_training_matches_single_device():
    pp4 = _train(_make_pl(), dict(pp=4, dp=2), n_micro=4)
    single = _train(_make_pl(), dict(dp=1, devices=jax.devices()[:1]),
                    n_micro=4)
    np.testing.assert_allclose(pp4, single, rtol=2e-4)


def test_pipeline_with_pre_post_matches_single_device():
    def build():
        paddle.seed(3)
        d = 16
        descs = ([LayerDesc(_Embed, d)] +
                 [LayerDesc(nn.Linear, d, d) for _ in range(8)] +
                 [LayerDesc(nn.LayerNorm, d)])
        return PipelineLayer(layers=descs, num_stages=4,
                             loss_fn=lambda o, l: jnp.mean((o - l) ** 2))

    pp4 = _train(build(), dict(pp=4, dp=2), n_micro=4)
    single = _train(build(), dict(dp=1, devices=jax.devices()[:1]),
                    n_micro=4)
    np.testing.assert_allclose(pp4, single, rtol=2e-4)


def test_fleet_pipeline_parallel_wrapper():
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import \
        PipelineParallel

    mesh = create_hybrid_mesh(pp=4, dp=2)
    set_hybrid_mesh(mesh)
    pl = _make_pl()

    class Strat:
        class hybrid_configs:
            micro_batch_size = 2
            accumulate_steps = 4
            schedule_mode = "1F1B"

    pp = PipelineParallel(pl, strategy=Strat)
    opt = AdamW(learning_rate=1e-2)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    y = rng.standard_normal((8, 16)).astype(np.float32)
    l0 = pp.train_batch((x, y), opt)
    l1 = pp.train_batch((x, y), opt)
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0


# ---------------------------------------------------------------------------
# Interleaved virtual stages (VPP) — ref PipelineParallelWithInterleave.
# ---------------------------------------------------------------------------

def test_spmd_pipeline_interleaved_matches_sequential():
    S, V, n_micro, mb, d = 4, 2, 8, 2, 8
    mesh = create_hybrid_mesh(pp=S, dp=2)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((S, V, d, d)) * 0.3, jnp.float32)
    x_mb = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage_fn(sp, x):
        return jnp.tanh(x @ sp)

    y = spmd_pipeline(stage_fn, w, x_mb, mesh, num_chunks=V)
    ref = x_mb
    for l in range(S * V):  # virtual stage l lives on device l%S, chunk l//S
        ref = jnp.tanh(ref @ w[l % S, l // S])
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)

    def loss_pipe(w):
        return jnp.mean(
            spmd_pipeline(stage_fn, w, x_mb, mesh, num_chunks=V) ** 2)

    def loss_seq(w):
        y = x_mb
        for l in range(S * V):
            y = jnp.tanh(y @ w[l % S, l // S])
        return jnp.mean(y ** 2)

    gp = jax.grad(loss_pipe)(w)
    gs = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(gp, gs, rtol=1e-4, atol=1e-6)


def test_spmd_pipeline_interleaved_rejects_few_microbatches():
    mesh = create_hybrid_mesh(pp=4, dp=2)
    x_mb = jnp.zeros((2, 2, 8), jnp.float32)  # n_micro=2 < pp=4
    with pytest.raises(ValueError, match="n_micro"):
        spmd_pipeline(lambda sp, x: x @ sp, jnp.zeros((4, 2, 8, 8)),
                      x_mb, mesh, num_chunks=2)


def test_vpp_training_matches_single_device():
    def build():
        paddle.seed(5)
        descs = [LayerDesc(nn.Linear, 16, 16) for _ in range(8)]
        return PipelineLayer(
            layers=descs, num_stages=4, num_virtual_pipeline_stages=2,
            loss_fn=lambda o, l: jnp.mean((o - l) ** 2))

    vpp = _train(build(), dict(pp=4, dp=2), n_micro=4)
    single = _train(build(), dict(dp=1, devices=jax.devices()[:1]),
                    n_micro=4)
    np.testing.assert_allclose(vpp, single, rtol=2e-4)


# ---------------------------------------------------------------------------
# Heterogeneous stages — lax.switch dispatch (no homogeneous trunk).
# ---------------------------------------------------------------------------

class _Block(nn.Layer):
    """Residual block — structurally distinct from plain Linear."""

    def __init__(self, d):
        super().__init__()
        self.a = nn.Linear(d, d)
        self.b = nn.Linear(d, d)

    def forward(self, x):
        return x + self.b(jnp.tanh(self.a(x)))


def _make_het_pl(seed=7, d=16):
    paddle.seed(seed)
    descs = [LayerDesc(nn.Linear, d, d), LayerDesc(_Block, d),
             LayerDesc(nn.LayerNorm, d), LayerDesc(_Block, d),
             LayerDesc(nn.Linear, d, d), LayerDesc(_Block, d),
             LayerDesc(nn.LayerNorm, d), LayerDesc(nn.Linear, d, d)]
    return PipelineLayer(layers=descs, num_stages=4,
                         loss_fn=lambda o, l: jnp.mean((o - l) ** 2))


def test_het_pipeline_training_matches_single_device():
    het = _train(_make_het_pl(), dict(pp=4, dp=2), n_micro=4)
    single = _train(_make_het_pl(), dict(dp=1, devices=jax.devices()[:1]),
                    n_micro=4)
    assert het[-1] < het[0]
    np.testing.assert_allclose(het, single, rtol=2e-4)


def test_het_pipeline_shape_mismatch_warns_and_falls_back():
    paddle.seed(9)
    descs = [LayerDesc(nn.Linear, 16, 32), LayerDesc(_Block, 32),
             LayerDesc(nn.Linear, 32, 16), LayerDesc(nn.LayerNorm, 16)]
    pl = PipelineLayer(layers=descs, num_stages=4,
                       loss_fn=lambda o, l: jnp.mean((o - l) ** 2))
    mesh = create_hybrid_mesh(pp=4, dp=2)
    set_hybrid_mesh(mesh)
    opt = AdamW(learning_rate=1e-2)
    step = make_pipeline_train_step(pl, opt, n_microbatch=4)
    params = get_params(pl)
    opt_state = opt.init(params)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)),
                    jnp.float32)
    with pytest.warns(UserWarning, match="falling back"):
        params, opt_state, loss = step(params, opt_state, x, x,
                                       jnp.float32(1e-2))
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# Serial (single-device) schedule emulation — the pp-machinery probe
# (ISSUE r6: measure the real 4-stage 1F1B with stages serially resident)
# ---------------------------------------------------------------------------

def test_spmd_pipeline_serial_matches_sequential():
    from paddle_tpu.distributed.pipeline_schedule import spmd_pipeline_serial
    S, n_micro, mb, d = 4, 6, 2, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((S, d)) * 0.1, jnp.float32)
    x_mb = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage_fn(sp, x):
        return jnp.tanh(x @ sp["w"] + sp["b"])

    y = spmd_pipeline_serial(stage_fn, {"w": w, "b": b}, x_mb, S,
                             remat=False)
    ref = x_mb
    for s in range(S):
        ref = jnp.tanh(ref @ w[s] + b[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_spmd_pipeline_serial_grads_match(mesh8=None):
    from paddle_tpu.distributed.pipeline_schedule import spmd_pipeline_serial
    S, n_micro, mb, d = 2, 4, 2, 8
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((S, d, d)) * 0.3, jnp.float32)
    x_mb = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def stage_fn(sp, x):
        return jnp.tanh(x @ sp)

    def loss_sched(w):
        return jnp.mean(
            spmd_pipeline_serial(stage_fn, w, x_mb, S, remat=True) ** 2)

    def loss_seq(w):
        y = x_mb
        for s in range(S):
            y = stage_fn(w[s], y)
        return jnp.mean(y ** 2)

    np.testing.assert_allclose(float(loss_sched(w)), float(loss_seq(w)),
                               rtol=1e-6)
    ga = jax.jit(jax.grad(loss_sched))(w)
    gb = jax.jit(jax.grad(loss_seq))(w)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=2e-5,
                               atol=1e-7)


def test_build_serial_probe_loss_and_grad_parity():
    """The two probe losses (emulated 1F1B schedule vs plain microbatch
    loop) must agree exactly on value and gradients — anything else and
    the machinery-overhead measurement compares different math."""
    from paddle_tpu.distributed.pipeline_schedule import build_serial_probe
    paddle.seed(0)
    descs = [LayerDesc(nn.Linear, 16, 16) for _ in range(4)]
    pl = PipelineLayer(layers=descs, num_stages=1,
                       loss_fn=lambda o, l: jnp.mean((o - l) ** 2))
    probe = build_serial_probe(pl, n_stages=4, n_microbatch=4)
    assert probe is not None
    loss_sched, loss_plain, analysis = probe
    assert analysis.homogeneous
    params = get_params(pl)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    a = float(jax.jit(loss_sched)(params, x, y))
    b = float(jax.jit(loss_plain)(params, x, y))
    np.testing.assert_allclose(a, b, rtol=1e-6)
    ga = jax.jit(jax.grad(loss_sched))(params, x, y)
    gb = jax.jit(jax.grad(loss_plain))(params, x, y)
    for k in ga:
        np.testing.assert_allclose(np.asarray(ga[k]), np.asarray(gb[k]),
                                   rtol=2e-4, atol=1e-6)


def test_build_serial_probe_rejects_non_homogeneous():
    from paddle_tpu.distributed.pipeline_schedule import build_serial_probe
    paddle.seed(0)
    descs = [LayerDesc(nn.Linear, 16, 16) for _ in range(2)]
    pl = PipelineLayer(layers=descs, num_stages=1,
                       loss_fn=lambda o, l: jnp.mean((o - l) ** 2))
    assert build_serial_probe(pl, n_stages=4, n_microbatch=4) is None
