"""Auto-parallel API tests (ProcessMesh / shard_tensor / shard_op / Engine).

Parity anchor: ref auto_parallel/interface.py + static/engine.py; the key
check (VERDICT r1 #5): a *plain* GPT-style layer sharded via shard_tensor
alone reproduces the mp_layers (ColumnParallel/RowParallel) placement and
numerics.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.auto_parallel import (Engine, ProcessMesh,
                                                  get_current_process_mesh,
                                                  shard_tensor, shard_op)
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)


def test_process_mesh_basics():
    pm = ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]], dim_names=["x", "y"])
    assert pm.shape == [2, 4]
    assert pm.process_ids == list(range(8))
    assert pm.get_dim_size("y") == 4
    assert pm.ndim == 2
    with pm:
        assert get_current_process_mesh() is pm
    assert get_current_process_mesh() is None
    pm2 = ProcessMesh(shape=[2, 4], process_ids=list(range(8)),
                      dim_names=["x", "y"])
    assert pm == pm2


def test_shard_tensor_placement():
    pm = ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])
    t = shard_tensor(np.zeros((6, 12), np.float32), pm, ["x", "y"])
    assert t.sharding == NamedSharding(pm.jax_mesh, P("x", "y"))
    # per-shard shape [3, 3]
    assert t.addressable_shards[0].data.shape == (3, 3)
    r = shard_tensor(np.zeros((6, 12), np.float32), pm, [None, "x"])
    assert r.addressable_shards[0].data.shape == (6, 6)
    rep = shard_tensor(np.zeros((4,), np.float32), pm)
    assert rep.sharding.is_fully_replicated


def test_shard_tensor_in_scope_and_in_jit():
    pm = ProcessMesh(np.arange(8), dim_names=["x"])
    with pm:
        t = shard_tensor(np.zeros((8, 4), np.float32), shard_spec=["x", None])
    assert t.addressable_shards[0].data.shape == (1, 4)

    @jax.jit
    def f(a):
        b = shard_tensor(a * 2, pm, ["x", None])
        return b + 1

    out = f(t)
    assert out.sharding.spec == P("x", None)


def test_shard_op_constrains_output():
    pm = ProcessMesh(np.arange(8), dim_names=["x"])
    mm = shard_op(jnp.matmul, pm, in_shard_specs=[["x", None], None],
                  out_shard_specs=[["x", None]])

    @jax.jit
    def f(a, b):
        return mm(a, b)

    a = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((4, 4)).astype(np.float32)
    out = f(a, b)
    np.testing.assert_allclose(out, a @ b, rtol=1e-5)
    assert out.sharding.spec[0] == "x"


def test_shard_tensor_reproduces_mp_layers_placement():
    """A plain two-matmul MLP with weights placed by shard_tensor alone must
    match the ColumnParallelLinear/RowParallelLinear placement (w1 split on
    out-dim, w2 split on in-dim) and the parallel layers' numerics."""
    from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)

    d, ffn = 16, 32
    mesh = create_hybrid_mesh(mp=4, dp=2)
    set_hybrid_mesh(mesh)
    paddle.seed(0)
    col = ColumnParallelLinear(d, ffn, gather_output=False, has_bias=False)
    row = RowParallelLinear(ffn, d, input_is_parallel=True, has_bias=False)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, d)),
                    jnp.float32)

    # reference numerics via the parallel layers
    y_ref = row(jax.nn.gelu(col(x)))

    # same weights placed purely by shard_tensor on the ProcessMesh facade
    pm = ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["dp", "mp"])
    w1 = shard_tensor(np.asarray(col.weight), pm, [None, "mp"])
    w2 = shard_tensor(np.asarray(row.weight), pm, ["mp", None])
    assert w1.sharding.spec == P(None, "mp")
    assert w2.sharding.spec == P("mp", None)

    @jax.jit
    def fwd(w1, w2, x):
        h = jax.nn.gelu(x @ w1)
        return h @ w2

    y = fwd(w1, w2, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_engine_fit_matches_single_device():
    def build_and_fit(pm):
        paddle.seed(11)
        model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 2))
        from paddle_tpu.optimizer import AdamW
        eng = Engine(model=model,
                     loss=lambda o, y: jnp.mean((o - y) ** 2),
                     optimizer=AdamW(learning_rate=1e-2), process_mesh=pm)
        rng = np.random.default_rng(5)
        data = []
        for _ in range(64):  # learnable mapping so loss actually decreases
            x = rng.standard_normal(8).astype(np.float32)
            data.append((x, (x[:2] * 0.5 + 0.1).astype(np.float32)))
        hist = eng.fit(data, epochs=2, batch_size=16, lr=1e-2)
        ev = eng.evaluate(data, batch_size=16)
        return hist, ev

    pm = ProcessMesh(np.arange(8).reshape(8,), dim_names=["dp"])
    h_dist, ev_dist = build_and_fit(pm)
    h_single, ev_single = build_and_fit(None)
    np.testing.assert_allclose(h_dist, h_single, rtol=1e-4)
    assert np.isfinite(ev_dist["loss"]) and abs(
        ev_dist["loss"] - ev_single["loss"]) < 1e-4
    assert h_dist[-1] < h_dist[0]
