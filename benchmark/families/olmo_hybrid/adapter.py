"""The program's Olmo-Hybrid at a configuration's sizes, the neutral leaf names
mapped to the program's, the ``ServingEngine`` a mix's ``engine`` block
describes and its warm-up. With ``benchmark/lib/system.py`` (what every
family shares) this is all of the benchmark that imports the program; nothing
here decides a metric."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import system
from benchmark.lib.weights import get_leaf

from . import weights as W

_COMMON = {
    "post_mix_g": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "post_ff_g": "post_feedforward_layernorm.weight",
    "w_q": "q_proj.weight", "w_k": "k_proj.weight", "w_v": "v_proj.weight",
    "w_o": "o_proj.weight",
}
_FULL = {"q_norm_g": "q_norm.weight", "k_norm_g": "k_norm.weight"}
_LINEAR = {"w_z": "g_proj.weight", "w_a": "a_proj.weight",
           "w_b": "b_proj.weight", "conv_w": "conv_weight",
           "a_log": "A_log", "dt_bias": "dt_bias",
           "o_norm_g": "o_norm.weight"}
#: leaves the program keeps in float32 whatever the weights' precision
FLOAT32 = ("a_log", "dt_bias")


def program_name(leaf: str) -> str:
    """The program's parameter name of a neutral leaf name."""
    parts = leaf.split(".")
    if parts[0] == "layers":
        name = parts[2]
        return (f"model.layers.{parts[1]}."
                f"{_COMMON.get(name) or _FULL.get(name) or _LINEAR[name]}")
    return {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
            "lnf_g": "model.norm.weight"}[leaf]


def to_program(cfg, weights) -> Dict[str, jax.Array]:
    """The leaves under the program's names, in the precision the
    configuration states for the weights (the generator's values are exact
    in bfloat16, so a cast up changes nothing), ``a_log`` and ``dt_bias`` in
    float32 as the published model keeps them, ``a_log`` plus the
    configuration's decay offset (``weights.a_log_offset``: the reference
    adds the same, exactly)."""
    dtype = cfg["precision"]["weights"]
    offset = jnp.asarray(W.a_log_offset(cfg))

    def leaf(n):
        kind = n.rsplit(".", 1)[-1]
        if kind not in FLOAT32:
            return get_leaf(weights, n).astype(dtype)
        a = get_leaf(weights, n).astype(jnp.float32)
        return a + offset if kind == "a_log" else a
    return {program_name(n): leaf(n) for n in W.leaf_names(cfg)}


def build_model(cfg, remat: bool):
    """The program's model at the configuration's widths, in the precision
    it states for the weights, its first ``num_hidden_layers`` of the
    published ``layer_types``. Created as zeros: :func:`load_weights` gives
    it its values."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.olmo_hybrid import (OlmoHybridConfig,
                                                    OlmoHybridForCausalLM)
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "max_position_embeddings", "layer_types",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")
    mcfg = OlmoHybridConfig(**{k: cfg[k] for k in same},
                            dtype=cfg["precision"]["weights"],
                            init_weights=False)
    paddle.seed(0)
    return OlmoHybridForCausalLM(mcfg)


def load_weights(model, cfg, weights) -> None:
    from paddle_tpu.framework.functional import set_params
    set_params(model, to_program(cfg, weights))


def loss_fn(model, params, batch):
    raise NotImplementedError(
        "the olmo_hybrid family has no training cell (reference.py)")


def build_engine(cfg, weights, eng_cfg):
    """``ServingEngine`` as the mix's ``engine`` block describes it. What the
    block leaves out: a pool that holds ``max_batch`` rows at
    ``max_seq_len``. The three ``serve_*`` tiers are off: a model whose
    layers keep a state a sequence is served without them."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, remat=False)
    load_weights(model, cfg, weights)
    blocks_per_seq = -(-eng_cfg["max_seq_len"] // eng_cfg["block_size"])
    return ServingEngine(
        model, block_size=eng_cfg["block_size"],
        num_blocks=eng_cfg.get("num_blocks",
                               eng_cfg["max_batch"] * blocks_per_seq + 1),
        max_batch=eng_cfg["max_batch"], max_seq_len=eng_cfg["max_seq_len"],
        prefill_buckets=eng_cfg["prefill_buckets"],
        decode_buckets=eng_cfg["decode_buckets"],
        prefix_cache=False, chunked_prefill=0, speculative=0)


def warm_engine(eng, cfg, eng_cfg) -> None:
    """Run every program this traffic uses once: each prefill bucket with a
    prompt that lands in it, the first twice (a fresh engine's first prefill
    sees pools no program has returned yet: a second signature), and the one
    decode width, which every request runs."""
    rng = np.random.default_rng(0)
    edges = [0] + sorted(eng_cfg["prefill_buckets"])
    lengths = [edges[1]] + [lo + 1 for lo in edges[:-1]]
    for n, length in enumerate(lengths):
        ids = rng.integers(0, cfg["vocab_size"], size=length)
        eng.submit(system.make_request(f"warm{n}", ids, 2))
        while eng.sched.n_pending:
            eng.step()
