"""MoE expert-parallel tests: the dropless layer, whole and as one rank's
share of the experts, on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.framework.functional import functional_call, get_params
from paddle_tpu.incubate.distributed.models.moe.moe_layer import MoELayer


def _x(b=2, s=16, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)


@pytest.mark.parametrize("gate", ["naive", "gshard", "switch"])
def test_moe_forward_shapes_and_aux(gate):
    paddle.seed(0)
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate=gate)
    layer.eval()
    y = layer(_x())
    assert y.shape == (2, 16, 8)
    assert np.isfinite(np.asarray(y)).all()
    assert float(layer.l_aux) >= 0


def test_moe_routes_tokens_to_top1_expert():
    """With capacity ample and top-1 gating, each token's output equals its
    chosen expert's FFN applied to it, scaled by the gate prob."""
    paddle.seed(1)
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=2, gate="naive",
                     capacity_factor=8.0)
    layer.eval()
    x = _x(b=1, s=4)
    y = layer(x)
    logits = jnp.matmul(x, layer.gate.weight)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    for t in range(4):
        e = int(idx[0, t])
        tok = x[0, t][None, None]
        w1, b1 = layer.experts.w1[e], layer.experts.b1[e]
        w2, b2 = layer.experts.w2[e], layer.experts.b2[e]
        from paddle_tpu.nn import functional as F
        h = F.gelu(tok[0] @ w1 + b1)
        ref = (h @ w2 + b2) * probs[0, t, e]
        np.testing.assert_allclose(y[0, t], ref[0], rtol=1e-4, atol=1e-5)


def test_moe_sharded_matches_single_device():
    def run(mesh_kwargs):
        paddle.seed(2)
        layer = MoELayer(d_model=8, d_hidden=16, num_experts=8, gate="gshard")
        layer.eval()
        mesh = create_hybrid_mesh(**mesh_kwargs)
        set_hybrid_mesh(mesh)
        params = get_params(layer)
        x = _x(b=4, s=16, seed=3)

        @jax.jit
        def f(p, x):
            return functional_call(layer, p, x, training=False)

        return np.asarray(f(params, x))

    single = run(dict(dp=1, devices=jax.devices()[:1]))
    ep = run(dict(mp=4, dp=2))  # expert dim rides the mp axis
    np.testing.assert_allclose(single, ep, rtol=1e-4, atol=1e-5)


def _dense_moe(layer, x):
    """Every token through its gate's experts, one pair at a time."""
    from paddle_tpu.nn import functional as F
    flat = x.reshape(-1, x.shape[-1])
    idx, weight, _ = layer.gate(flat)
    out = np.zeros(flat.shape, np.float32)
    for t in range(flat.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - layer.first
            if not 0 <= e < layer.count:
                continue
            h = F.gelu(flat[t] @ layer.experts.w1[e] + layer.experts.b1[e, 0])
            out[t] += float(weight[t, j]) * np.asarray(
                h @ layer.experts.w2[e] + layer.experts.b2[e, 0])
    return out.reshape(x.shape)


@pytest.mark.parametrize("gate", ["naive", "gshard", "switch"])
def test_moe_is_dropless_at_an_imbalanced_load(gate):
    """All tokens to one expert (the gate's other columns pushed far down):
    every one of them is computed; a capacity bucket would have cut all but
    a few off."""
    paddle.seed(3)
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate=gate)
    layer.eval()
    x = jnp.abs(_x(b=2, s=32, seed=5)) + 0.1
    w = np.full((8, 4), -5.0, np.float32)
    w[:, 2] = 5.0
    layer.gate.weight = jnp.asarray(w)
    y = layer(x)
    load = np.asarray(layer.expert_load)
    assert load[2] == 64 and load.sum() == 64 * layer.gate.top_k
    np.testing.assert_allclose(y, _dense_moe(layer, x), rtol=1e-4, atol=1e-5)


def test_moe_shares_add_up_to_the_whole_layer():
    """Four ranks of two experts each: what each computes for the experts it
    holds, summed, is the layer that holds all eight."""
    paddle.seed(4)
    whole = MoELayer(d_model=8, d_hidden=16, num_experts=8, gate="gshard")
    whole.eval()
    x = _x(b=2, s=24, seed=6)
    want = whole(x)
    assert int(np.asarray(whole.expert_load).sum()) == 2 * 48
    total = 0.0
    for rank in range(4):
        share = MoELayer(d_model=8, d_hidden=16, num_experts=8, gate="gshard",
                         experts_held=(2 * rank, 2))
        share.eval()
        share.gate.weight = whole.gate.weight
        sl = slice(2 * rank, 2 * rank + 2)
        for name in ("w1", "b1", "w2", "b2"):
            setattr(share.experts, name, getattr(whole.experts, name)[sl])
        total = total + share(x)
        np.testing.assert_array_equal(share.expert_load,
                                      np.asarray(whole.expert_load)[sl])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="experts_held"):
        MoELayer(d_model=8, d_hidden=16, num_experts=8, experts_held=(7, 2))


def test_moe_trains():
    paddle.seed(0)
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate="gshard")
    params = get_params(layer)
    x = _x(b=4, s=16)
    target = jnp.roll(x, 1, axis=-1)

    def loss_fn(p):
        y = functional_call(layer, p, x, training=True)
        return jnp.mean((y - target) ** 2)

    g = jax.grad(loss_fn)(params)
    # Gradients reach the gate and at least some experts.
    assert float(jnp.abs(g["gate.weight"]).sum()) > 0
    assert float(jnp.abs(g["experts.w1"]).sum()) > 0


def test_group_sharded_parallel_stage3_stamps_specs():
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.meta_parallel.sharding import \
        group_sharded_parallel
    from paddle_tpu.optimizer import AdamW

    mesh = create_hybrid_mesh(sharding=8)
    set_hybrid_mesh(mesh)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 16))
    opt = AdamW(learning_rate=1e-3, parameters=net.parameters())
    net, opt, _ = group_sharded_parallel(net, opt, level="p_g_os")
    specs = [ref.meta.partition_spec for _, ref in net.named_parameters()]
    assert any(s is not None and "sharding" in str(s) for s in specs)
