"""The program's DeepSeek-V2 at a configuration's sizes and share, the
neutral leaf names mapped to the program's, and the ``ServingEngine`` a
mix's ``engine`` block describes. With ``benchmark/lib/system.py`` (what
every family shares) this is all of the benchmark that imports the program;
nothing here decides a metric."""

from __future__ import annotations

from typing import Dict

import jax

from benchmark.lib.weights import get_leaf

from . import weights as W

_LAYER_NAMES = {
    "ln1_g": "input_layernorm.weight",
    "w_dq": "self_attn.q_a_proj.weight",
    "q_norm_g": "self_attn.q_a_layernorm.weight",
    "w_uq": "self_attn.q_b_proj.weight",
    "w_dkv": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_norm_g": "self_attn.kv_a_layernorm.weight",
    "w_ukv": "self_attn.kv_b_proj.weight",
    "w_o": "self_attn.o_proj.weight",
    "ln2_g": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "w_router": "mlp.router.weight",
    "ws_gate": "mlp.shared_experts.gate_proj.weight",
    "ws_up": "mlp.shared_experts.up_proj.weight",
    "ws_down": "mlp.shared_experts.down_proj.weight",
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
    configuration's scale for it (``weights.init_scale``: 1 but for the
    router and the routed experts' down-projection, powers of two)."""
    dtype = cfg["precision"]["weights"]

    def leaf(n):                      # the reference applies the scale too
        return (get_leaf(weights, n) * W.init_scale(cfg, n)).astype(dtype)
    return {program_name(n): leaf(n) for n in W.leaf_names(cfg)}


def build_model(cfg, remat: bool):
    """The program's model at the configuration's widths, in the precision
    it states for the weights (bfloat16 in the benchmark's), told which
    experts it holds: the router keeps ``router_width`` outputs, the layer
    holds ``n_routed_experts`` from ``experts_held_first``. Created as zeros:
    :func:`load_weights` gives it its values."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2Config,
                                                    DeepseekV2ForCausalLM)
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts",
            "num_experts_per_tok", "n_group", "topk_group",
            "routed_scaling_factor", "norm_topk_prob",
            "first_k_dense_replace", "rms_norm_eps", "rope_theta",
            "rope_scaling", "max_position_embeddings")
    mcfg = DeepseekV2Config(
        **{k: cfg[k] for k in same}, n_routed_experts=W.router_width(cfg),
        experts_held=(cfg.get("experts_held_first", 0),
                      cfg["n_routed_experts"]),
        dtype=cfg["precision"]["weights"], init_weights=False)
    paddle.seed(0)
    return DeepseekV2ForCausalLM(mcfg)


def load_weights(model, cfg, weights) -> None:
    from paddle_tpu.framework.functional import set_params
    set_params(model, to_program(cfg, weights))


def loss_fn(model, params, batch):
    raise NotImplementedError(
        "the deepseek_v2 family has no training cell (reference.py)")


def build_engine(cfg, weights, eng_cfg):
    """``ServingEngine`` as the mix's ``engine`` block describes it. What the
    block leaves out: the three ``serve_*`` tiers off, a pool that holds
    ``max_batch`` rows at ``max_seq_len``."""
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
        prefix_cache=eng_cfg.get("prefix_cache", False),
        chunked_prefill=eng_cfg.get("chunked_prefill", 0),
        speculative=eng_cfg.get("speculative", 0))
