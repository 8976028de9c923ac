"""The system under test, built the way ``chip_smoke.py`` builds it. This is the
only file of the benchmark that imports the program; from it the benchmark
takes the model, the compiled train step and the serving engine, and nothing
that decides a metric.
"""

from __future__ import annotations

import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

_LAYER_NAMES = {
    "ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
    "w_qkv": "attn.qkv_proj.weight", "b_qkv": "attn.qkv_proj.bias",
    "w_o": "attn.out_proj.weight", "b_o": "attn.out_proj.bias",
    "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
    "w_up": "mlp.up.weight", "b_up": "mlp.up.bias",
    "w_down": "mlp.down.weight", "b_down": "mlp.down.bias",
}


def program_name(leaf: str) -> str:
    """The program's parameter name of a neutral leaf name."""
    parts = leaf.split(".")
    if parts[0] == "layers":
        return f"gpt.h.{parts[1]}.{_LAYER_NAMES[parts[2]]}"
    return {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
            "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}[leaf]


def to_program(cfg, weights) -> Dict[str, jax.Array]:
    return {program_name(n): W.get_leaf(weights, n)
            for n in W.leaf_names(cfg)}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else a fixed directory inside the checkout.
    The program's own ``enable_compile_cache`` picks the same directory, so
    both agree whoever is called first."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_model(cfg, remat: bool):
    """The program's GPT at the configuration's sizes, bf16 (AMP O2). Its own
    random init is overwritten by :func:`load_weights`."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        hidden_dropout=0.0, attention_dropout=0.0, recompute=remat)
    paddle.seed(0)
    model = GPTForCausalLM(gcfg)
    model.astype(paddle.bfloat16)
    return model


def load_weights(model, cfg, weights) -> None:
    from paddle_tpu.framework.functional import set_params
    set_params(model, to_program(cfg, weights))


def loss_fn(model, params, batch):
    from paddle_tpu.framework.functional import functional_call
    ids, labels = batch
    return functional_call(model, params, ids, labels, training=True)


def build_mesh(mesh_axes, chips: int):
    """A one-device ``dp`` mesh, or the hybrid mesh a cell's file names."""
    from jax.sharding import Mesh
    devs = jax.devices()[:chips]
    if not mesh_axes:
        return Mesh(np.asarray(devs[:1]), ("dp",))
    from paddle_tpu.distributed.topology import create_hybrid_mesh
    return create_hybrid_mesh(devices=devs, **mesh_axes)


def replicated(mesh):
    """The sharding that puts a whole copy on every chip of a sharded cell's
    mesh; None (the default device) on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec()) if mesh.size > 1 else None


def build_train_step(cfg, weights, opt_cfg, mesh):
    """``make_sharded_train_step`` over the program's GPT with resident
    AdamW and fp32 masters; the Layer tree reads the step's own buffers, so
    one set of parameters is resident."""
    from paddle_tpu.framework.sharded import (make_sharded_train_step,
                                              shard_params)
    from paddle_tpu.optimizer import AdamW
    model = build_model(cfg, remat=True)
    load_weights(model, cfg, weights)
    model.train()
    if mesh.size > 1:
        shard_params(model, mesh)
    opt = AdamW(learning_rate=opt_cfg["learning_rate"],
                beta1=opt_cfg["beta1"], beta2=opt_cfg["beta2"],
                epsilon=opt_cfg["epsilon"],
                weight_decay=opt_cfg["weight_decay"], multi_precision=True)
    ts = make_sharded_train_step(model, opt, loss_fn, mesh=mesh)
    ts.sync_to_model()
    return ts


def reset_train_step(ts, cfg, weights) -> None:
    """Put fresh weights and a zero optimizer state into a live step (the
    compiled program is kept): for reading many seeds in one process. The old
    state is dropped first; two do not fit."""
    import gc
    load_weights(ts.model, cfg, weights)
    ts.params, ts.opt_state = {}, None
    gc.collect()
    params = {n: jax.device_put(v, ts.pshardings[n])
              for n, v in to_program(cfg, weights).items()}
    # born sharded like the step's own state: built eagerly, every moment of
    # a sharded cell would land whole on chip 0 (10.5 GB at 24 layers)
    init = jax.jit(ts.optimizer.init, out_shardings=ts._state_shardings) \
        if ts.mesh.size > 1 else ts.optimizer.init
    ts.load_state_dict({"params": params, "opt_state": init(params),
                        "buffers": {}, "step_count": 0, "lr_sched": None})
    ts.sync_to_model()


def train_state_norms(ts, cfg, beta1: float, start_seed=None):
    """What the comparison reads from the optimizer's state: per compared
    leaf the norm of the first gradient as the optimizer got it (moment1
    after one step over 1 - beta1) or, given the seed the weights came from,
    the norm of master - start. The start is made again inside the same
    program, leaf by leaf, so that no second copy of the model is resident
    and the memory peak stays the program's own."""
    states = ts.opt_state["param_states"]

    def norms(leaf_of):
        out = {}
        for n in W.leaf_names(cfg):
            for part, a in W.compared_parts(n, leaf_of(n)).items():
                out[part] = jnp.sqrt(jnp.sum(jnp.square(a)))
        return out

    @jax.jit
    def first_grad(states):
        return norms(lambda n: states[program_name(n)]["moment1"]
                     / (1.0 - beta1))

    @jax.jit
    def delta(states, key):
        w0 = W.weights_from_key(key, cfg, jnp.bfloat16)
        return norms(lambda n: states[program_name(n)]["master"]
                     - W.get_leaf(w0, n).astype(jnp.float32))

    out = first_grad(states) if start_seed is None \
        else delta(states, W.seed_key(start_seed))
    return {k: float(v) for k, v in out.items()}


def build_engine(cfg, weights, eng_cfg):
    """``ServingEngine`` with the three ``serve_*`` tiers off, a pool that
    holds ``max_batch`` rows at ``max_seq_len``."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, remat=False)
    load_weights(model, cfg, weights)
    blocks_per_seq = -(-eng_cfg["max_seq_len"] // eng_cfg["block_size"])
    eng = ServingEngine(
        model, block_size=eng_cfg["block_size"],
        num_blocks=eng_cfg["max_batch"] * blocks_per_seq + 1,
        max_batch=eng_cfg["max_batch"], max_seq_len=eng_cfg["max_seq_len"],
        prefill_buckets=eng_cfg["prefill_buckets"],
        decode_buckets=eng_cfg["decode_buckets"],
        prefix_cache=False, chunked_prefill=0, speculative=0)
    return eng


def make_request(rid: str, prompt_ids, max_new_tokens: int):
    from paddle_tpu.serving import Request
    return Request(rid, np.asarray(prompt_ids, np.int32),
                   max_new_tokens=int(max_new_tokens))


def request_ok(seq) -> bool:
    from paddle_tpu.serving import Status
    return getattr(seq, "status", None) is Status.FINISHED


def request_failed(seq) -> bool:
    """Refused at admission, or retired in any way but finished."""
    from paddle_tpu.serving import Rejected, Status, TERMINAL_STATUSES
    if isinstance(seq, Rejected):
        return True
    return seq.status in TERMINAL_STATUSES and seq.status is not Status.FINISHED
