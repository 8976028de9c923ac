"""Deterministic-loss mode: bitwise parity dp=1 vs dp=8 (BASELINE north
star; SURVEY §7 hard part (d) — reduction order + RNG discipline)."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework.determinism import (deterministic_mode,
                                              is_deterministic,
                                              make_deterministic_dp_step)
from paddle_tpu.framework.functional import functional_call, get_params
from paddle_tpu.optimizer import SGD

import pytest  # noqa: E402

GROUPS = 8


def _setup():
    paddle.seed(0)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(16, 64)
            self.fc2 = nn.Linear(64, 1)

        def forward(self, x):
            return self.fc2(jax.nn.relu(self.fc1(x)))

    net = Net()
    params = get_params(net)

    def loss_fn(p, batch, key):
        x, y = batch
        pred = functional_call(net, p, x)
        # key reserved for dropout-style use; fold it in as a no-op so the
        # signature is exercised
        del key
        return jnp.mean((pred - y) ** 2)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((64, 1)), jnp.float32)
    return params, loss_fn, (x, y)


def _run(params, loss_fn, batch, mesh, steps=4):
    opt = SGD(learning_rate=0.05)
    opt_state = opt.init(params)
    step = make_deterministic_dp_step(loss_fn, opt, GROUPS, mesh=mesh)
    losses = []
    for i in range(steps):
        loss, params, opt_state = step(params, opt_state, batch,
                                       jnp.asarray(i))
        losses.append(np.asarray(loss))
    return np.asarray(losses), params


def test_flag_toggles():
    assert not is_deterministic()
    deterministic_mode(True)
    assert is_deterministic()
    deterministic_mode(False)
    assert not is_deterministic()


def test_bitwise_parity_dp1_vs_dp8():
    params, loss_fn, batch = _setup()
    losses_1, params_1 = _run(params, loss_fn, batch, mesh=None)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("dp",))
    losses_8, params_8 = _run(params, loss_fn, batch, mesh=mesh)

    # BITWISE identical — not allclose
    np.testing.assert_array_equal(losses_1, losses_8)
    for k in params_1:
        np.testing.assert_array_equal(np.asarray(params_1[k]),
                                      np.asarray(params_8[k]))


def test_bitwise_reproducible_run_to_run():
    params, loss_fn, batch = _setup()
    l1, _ = _run(params, loss_fn, batch, mesh=None)
    l2, _ = _run(params, loss_fn, batch, mesh=None)
    np.testing.assert_array_equal(l1, l2)


def test_losses_actually_decrease():
    params, loss_fn, batch = _setup()
    losses, _ = _run(params, loss_fn, batch, mesh=None, steps=6)
    assert losses[-1] < losses[0]
