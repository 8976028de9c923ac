"""Hybrid-parallel train step tests on the 8-device CPU mesh.

Model of SURVEY §4's distributed test strategy: loss parity between a
single-device run and an N-device hybrid-parallel (dp × fsdp × mp) run of the
same model/seed (the analog of the reference's TestDistBase two-process loss
comparison, without processes — the mesh is the cluster)."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.topology import create_hybrid_mesh
from paddle_tpu.framework.functional import functional_call, get_params
from paddle_tpu.framework.sharded import (infer_param_specs,
                                          make_sharded_train_step)
from paddle_tpu.optimizer import AdamW, SGD
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

from jax.sharding import Mesh, PartitionSpec as P


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                    max_position_embeddings=64, hidden_dropout=0.0,
                    attention_dropout=0.0, use_flash_attention=False)
    return GPTForCausalLM(cfg), cfg


def _batch(cfg, batch=8, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    return ids, labels


def _loss_fn(model, params, batch):
    ids, labels = batch
    return functional_call(model, params, ids, labels, training=True)


def _run_steps(mesh_kwargs, n_steps=3, opt_cls=AdamW):
    model, cfg = _tiny_gpt()
    if mesh_kwargs == dict(dp=1):  # single-device baseline
        mesh_kwargs = dict(dp=1, devices=jax.devices()[:1])
    mesh = create_hybrid_mesh(**mesh_kwargs)
    ts = make_sharded_train_step(model, opt_cls(learning_rate=1e-2),
                                 _loss_fn, mesh=mesh)
    losses = []
    for i in range(n_steps):
        losses.append(float(ts.step(_batch(cfg, seed=i))))
    return losses


def test_dp_matches_single_device():
    single = _run_steps(dict(dp=1))
    dp8 = _run_steps(dict(dp=8))
    np.testing.assert_allclose(single, dp8, rtol=2e-4)


def test_hybrid_dp_fsdp_mp_matches_single_device():
    single = _run_steps(dict(dp=1))
    hybrid = _run_steps(dict(dp=2, sharding=2, mp=2))
    np.testing.assert_allclose(single, hybrid, rtol=2e-4)


def test_mp_only_matches_single_device():
    single = _run_steps(dict(dp=1))
    mp8 = _run_steps(dict(mp=8, dp=1))
    # vocab 256 over mp=8 = 32 per shard; hidden 64 over 8 = 8.
    np.testing.assert_allclose(single, mp8, rtol=2e-4)


def test_loss_decreases():
    model, cfg = _tiny_gpt()
    mesh = create_hybrid_mesh(dp=2, sharding=2, mp=2)
    ts = make_sharded_train_step(model, AdamW(learning_rate=1e-2), _loss_fn,
                                 mesh=mesh)
    batch = _batch(cfg, seed=0)  # overfit one fixed batch
    losses = [float(ts.step(batch)) for _ in range(8)]
    assert losses[-1] < losses[0]


def test_infer_param_specs_fsdp_folding():
    devs = np.asarray(jax.devices()).reshape(2, 2, 2)
    mesh = Mesh(devs, ("dp", "sharding", "mp"))
    params = {
        "w_mp": jnp.zeros((64, 32)),
        "plain": jnp.zeros((64, 32)),
        "tiny": jnp.zeros((3,)),
    }
    user = {"w_mp": P(None, "mp"), "plain": None, "tiny": None}
    specs = infer_param_specs(params, user, mesh, fsdp_axis="sharding")
    # FSDP axis folds onto the largest unsharded dim.
    assert specs["w_mp"] == P("sharding", "mp")
    assert specs["plain"] == P("sharding", None)
    # Too small / indivisible params stay replicated.
    assert specs["tiny"] == P(None)


def test_specs_dropped_on_missing_axes():
    devs = np.asarray(jax.devices())
    mesh = Mesh(devs, ("dp",))
    params = {"w": jnp.zeros((64, 32))}
    specs = infer_param_specs(params, {"w": P(None, "mp")}, mesh,
                              fsdp_axis=None)
    assert specs["w"] == P(None, None)


def test_params_actually_sharded():
    model, cfg = _tiny_gpt()
    mesh = create_hybrid_mesh(dp=2, sharding=2, mp=2)
    ts = make_sharded_train_step(model, SGD(learning_rate=0.1), _loss_fn,
                                 mesh=mesh)
    qkv = next(v for n, v in ts.params.items() if "qkv_proj.weight" in n)
    # Column-parallel: out dim over mp; fsdp folds onto the in dim.
    shard_shape = qkv.sharding.shard_shape(qkv.shape)
    assert shard_shape[1] == qkv.shape[1] // 2
    assert shard_shape[0] == qkv.shape[0] // 2
