"""The program's SDAR-MoE at a configuration's sizes and share, the neutral
leaf names mapped to the program's, the ``ServingEngine`` a mix's ``engine``
block describes and its warm-up. With ``benchmark/lib/system.py`` (what every
family shares) this is all of the benchmark that imports the program; nothing
here decides a metric."""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from benchmark.lib import system
from benchmark.lib.weights import get_leaf

from . import weights as W

_LAYER_NAMES = {
    "ln1_g": "input_layernorm.weight",
    "w_q": "self_attn.q_proj.weight", "w_k": "self_attn.k_proj.weight",
    "w_v": "self_attn.v_proj.weight", "w_o": "self_attn.o_proj.weight",
    "q_norm_g": "self_attn.q_norm.weight",
    "k_norm_g": "self_attn.k_norm.weight",
    "ln2_g": "post_attention_layernorm.weight",
    "w_router": "mlp.router.weight",
    "we_gate": "mlp.w_gate", "we_up": "mlp.w_up", "we_down": "mlp.w_down",
}


def program_name(leaf: str) -> str:
    """The program's parameter name of a neutral leaf name."""
    parts = leaf.split(".")
    if parts[0] == "layers":
        return f"model.layers.{parts[1]}.{_LAYER_NAMES[parts[2]]}"
    return {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
            "lnf_g": "model.norm.weight"}[leaf]


def to_program(cfg, weights) -> Dict[str, jax.Array]:
    """The leaves under the program's names, in the precision the
    configuration states for the weights (the generator's values are exact
    in bfloat16, so a cast up changes nothing), each times the
    configuration's scale for it (``weights.init_scale``: 1 where it gives
    none, else a power of two)."""
    dtype = cfg["precision"]["weights"]

    def leaf(n):                      # the reference applies the scale too
        return (get_leaf(weights, n) * W.init_scale(cfg, n)).astype(dtype)
    return {program_name(n): leaf(n) for n in W.leaf_names(cfg)}


def build_model(cfg, remat: bool):
    """The program's model at the configuration's widths, in the precision
    it states for the weights, told which experts it holds (the router keeps
    ``router_width`` outputs, the layer holds ``num_experts`` from
    ``experts_held_first``) and how it generates (``generation``). Created
    as zeros: :func:`load_weights` gives it its values."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.sdar_moe import (SdarMoeConfig,
                                                 SdarMoeForCausalLM)
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "max_position_embeddings")
    gen = cfg["generation"]
    mcfg = SdarMoeConfig(
        **{k: cfg[k] for k in same}, num_experts=W.router_width(cfg),
        experts_held=(cfg.get("experts_held_first", 0), cfg["num_experts"]),
        block_length=gen["block_length"],
        denoising_steps=gen["denoising_steps"],
        confidence_threshold=gen["confidence_threshold"],
        mask_token_id=gen["mask_token_id"],
        dtype=cfg["precision"]["weights"], init_weights=False)
    paddle.seed(0)
    return SdarMoeForCausalLM(mcfg)


def load_weights(model, cfg, weights) -> None:
    from paddle_tpu.framework.functional import set_params
    set_params(model, to_program(cfg, weights))


def loss_fn(model, params, batch):
    raise NotImplementedError(
        "the sdar_moe family has no training cell (reference.py)")


def build_engine(cfg, weights, eng_cfg):
    """``ServingEngine`` as the mix's ``engine`` block describes it. What the
    block leaves out: a pool that holds ``max_batch`` rows at
    ``max_seq_len``. The three ``serve_*`` tiers are off: a model that
    generates by diffusion over blocks is served without them."""
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
    """Run every program this traffic uses once. The default warm-up's
    prompts of 2 tokens have no prefill pass here (a prompt shorter than a
    block opens its first block at position 0), so it would leave the
    smallest bucket cold: each prefill bucket is warmed with a prompt that
    fills it (the first one twice: a fresh engine's first prefill sees a
    pool no program has returned yet, PERF.md PR 33), then a prompt without
    a prefill pass; the block-decode program has one width and every request
    runs it."""
    rng = np.random.default_rng(0)
    block = cfg["generation"]["block_length"]
    buckets = sorted(eng_cfg["prefill_buckets"])
    lengths = [buckets[0]] + buckets + [block - 1]
    for n, length in enumerate(lengths):
        ids = rng.integers(0, cfg["vocab_size"], size=length)
        eng.submit(system.make_request(f"warm{n}", ids, block + 1))
        while eng.sched.n_pending:
            eng.step()
