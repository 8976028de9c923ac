"""Checkpoint tests incl. topology reshard (VERDICT r1 #10).

Parity anchor: the reference's per-rank shard saves + auto-parallel
``static/dist_saver.py`` / ``converter.py`` reshard-on-load. Here: save
under mesh A (dp x mp), restore under mesh B (fsdp) and single-device, and
assert bitwise equality of the gathered params. Also covers save/load of a
full train state (params + optimizer state) and resume parity.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.checkpoint import (load_sharded, load_state,
                                               save_sharded, save_state)
from paddle_tpu.distributed.topology import create_hybrid_mesh, set_hybrid_mesh
from paddle_tpu.framework.functional import get_params


def _params_on_mesh_a():
    """Params placed under mesh A: dp2 x mp4, weights sharded over mp."""
    mesh = create_hybrid_mesh(dp=2, mp=4)
    paddle.seed(7)
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 16))
    params = get_params(model)
    placed = {}
    for k, v in params.items():
        spec = P(None, "mp") if v.ndim == 2 else P()
        placed[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return placed, mesh


def test_save_mesh_a_restore_mesh_b_bitwise(tmp_path):
    placed, mesh_a = _params_on_mesh_a()
    host_copy = {k: np.asarray(v) for k, v in placed.items()}
    save_sharded(placed, str(tmp_path / "ckpt"))

    # Restore under mesh B: pure fsdp(8) row sharding — a different topology.
    mesh_b = create_hybrid_mesh(sharding=8)
    template, shardings = {}, {}
    for k, v in placed.items():
        template[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
        spec = P("sharding") if v.ndim == 2 and v.shape[0] % 8 == 0 else P()
        shardings[k] = NamedSharding(mesh_b, spec)
    restored = load_sharded(str(tmp_path / "ckpt"), template=template,
                            shardings=shardings)

    for k in host_copy:
        assert restored[k].sharding == shardings[k], k
        np.testing.assert_array_equal(np.asarray(restored[k]), host_copy[k])


def test_restore_single_device(tmp_path):
    placed, _ = _params_on_mesh_a()
    host_copy = {k: np.asarray(v) for k, v in placed.items()}
    save_sharded(placed, str(tmp_path / "ckpt"))
    set_hybrid_mesh(None)
    dev = jax.devices()[0]
    template = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in placed.items()}
    shardings = {k: jax.sharding.SingleDeviceSharding(dev) for k in placed}
    restored = load_sharded(str(tmp_path / "ckpt"), template=template,
                            shardings=shardings)
    for k in host_copy:
        np.testing.assert_array_equal(np.asarray(restored[k]), host_copy[k])


def test_train_state_save_resume_parity(tmp_path):
    """Training N+M steps straight must equal training N, checkpointing
    (params + opt state), restoring, and training M more."""
    from paddle_tpu.framework.functional import functional_call
    from paddle_tpu.optimizer import AdamW

    def make():
        paddle.seed(3)
        model = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 2))
        opt = AdamW(learning_rate=1e-2)
        params = get_params(model)
        return model, opt, params

    def steps(model, opt, params, opt_state, data):
        @jax.jit
        def step(p, s, x, y):
            def loss_of(p):
                out = functional_call(model, p, x, training=True)
                return jnp.mean((out - y) ** 2)
            loss, g = jax.value_and_grad(loss_of)(p)
            p2, s2 = opt.apply_gradients(p, g, s, 1e-2)
            return p2, s2, loss
        losses = []
        for x, y in data:
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        return params, opt_state, losses

    rng = np.random.default_rng(0)
    data = [(jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
             jnp.asarray(rng.standard_normal((4, 2)), jnp.float32))
            for _ in range(6)]

    # straight run
    model, opt, params = make()
    st = opt.init(params)
    _, _, straight = steps(model, opt, params, st, data)

    # checkpointed run
    model, opt, params = make()
    st = opt.init(params)
    params, st, first = steps(model, opt, params, st, data[:3])
    save_state({"params": params, "opt": st}, str(tmp_path / "state.pdparams"))
    loaded = load_state(str(tmp_path / "state.pdparams"))
    lp = jax.tree_util.tree_map(jnp.asarray, loaded["params"])
    ls = jax.tree_util.tree_map(jnp.asarray, loaded["opt"])
    _, _, rest = steps(model, opt, lp, ls, data[3:])
    np.testing.assert_allclose(first + rest, straight, rtol=1e-6)


# ---------------------------------------------------------------------------
# framework.io.save atomicity (ISSUE 7 satellite): a mid-write death must
# never leave a truncated file where load expects a checkpoint
# ---------------------------------------------------------------------------

_KILL_MID_WRITE = """
import os, pickle, signal, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from paddle_tpu.framework import io as fio

def killing_dump(obj, f, protocol=4):
    f.write(b"TRUNCATED GARBAGE")   # a partial, unloadable payload
    f.flush()
    os.fsync(f.fileno())
    os.kill(os.getpid(), signal.SIGKILL)   # die mid-write, no cleanup

fio.pickle.dump = killing_dump
fio.save({{"x": 1}}, {path!r})
"""


def _run_killed_save(path):
    import subprocess
    import sys
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         _KILL_MID_WRITE.format(repo=REPO, path=str(path))],
        capture_output=True, timeout=120)
    assert proc.returncode == -9, proc.stderr  # SIGKILLed as scripted


def test_save_killed_mid_write_preserves_previous_file(tmp_path):
    """Overwrite case: the old checkpoint must survive a death inside the
    replacement's write (seeded deterministic kill inside pickle.dump)."""
    from paddle_tpu.framework import io as fio
    path = tmp_path / "ckpt.pdparams"
    fio.save({"x": np.arange(4)}, str(path))
    _run_killed_save(path)
    loaded = fio.load(str(path))  # must still be the OLD content
    np.testing.assert_array_equal(np.asarray(loaded["x"]), np.arange(4))
    # the torn bytes live only in a tmp file load never looks at
    leftovers = [n for n in os.listdir(tmp_path) if ".tmp." in n]
    assert leftovers, "expected the torn tmp file to be left behind"


def test_save_killed_mid_write_first_save_leaves_no_file(tmp_path):
    """Fresh-path case: a death during the very first save must leave the
    target absent (not truncated) so resume logic falls back cleanly."""
    path = tmp_path / "fresh.pdparams"
    _run_killed_save(path)
    assert not path.exists()


def test_save_success_leaves_no_tmp(tmp_path):
    from paddle_tpu.framework import io as fio
    path = tmp_path / "clean.pdparams"
    fio.save({"x": 3}, str(path))
    assert fio.load(str(path))["x"] == 3
    assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []
