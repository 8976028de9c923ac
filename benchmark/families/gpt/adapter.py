"""The program's GPT, built the way ``chip_smoke.py`` builds it: the model at
a configuration's sizes, the neutral leaf names mapped to the program's, the
loss for ``TrainStep`` and the ``ServingEngine`` a mix's ``engine`` block
describes. With ``benchmark/lib/system.py`` (what every family shares) this is
all of the benchmark that imports the program; nothing here decides a metric.
"""

from __future__ import annotations

from typing import Dict

import jax

from benchmark.lib.weights import get_leaf

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
    return {program_name(n): get_leaf(weights, n)
            for n in W.leaf_names(cfg)}


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
