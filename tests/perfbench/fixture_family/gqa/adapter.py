"""The program's GPT with grouped-query attention, and its engine with the
prefix cache as the mix's ``engine`` block says. A fixture."""

from benchmark.lib import system
from benchmark.lib.weights import get_leaf

from . import weights as W

_LAYER_NAMES = {
    "ln1_scale": "ln_1.weight", "ln1_shift": "ln_1.bias",
    "w_q": "attn.q_proj.weight", "b_q": "attn.q_proj.bias",
    "w_kv": "attn.kv_proj.weight", "b_kv": "attn.kv_proj.bias",
    "w_o": "attn.out_proj.weight", "b_o": "attn.out_proj.bias",
    "ln2_scale": "ln_2.weight", "ln2_shift": "ln_2.bias",
    "w_up": "mlp.up.weight", "b_up": "mlp.up.bias",
    "w_down": "mlp.down.weight", "b_down": "mlp.down.bias",
}
WARMED = []     # the engine blocks this adapter's own warm-up was given


def program_name(leaf: str) -> str:
    parts = leaf.split(".")
    if parts[0] == "layers":
        return f"gpt.h.{parts[1]}.{_LAYER_NAMES[parts[2]]}"
    return {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
            "lnf_scale": "gpt.ln_f.weight", "lnf_shift": "gpt.ln_f.bias"}[leaf]


def to_program(cfg, weights):
    return {program_name(n): get_leaf(weights, n) for n in W.leaf_names(cfg)}


def build_model(cfg, remat: bool):
    import paddle_tpu as paddle
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_kv_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        hidden_dropout=0.0, attention_dropout=0.0, recompute=remat))
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
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, remat=False)
    load_weights(model, cfg, weights)
    return ServingEngine(
        model, block_size=eng_cfg["block_size"],
        num_blocks=eng_cfg["num_blocks"], max_batch=eng_cfg["max_batch"],
        max_seq_len=eng_cfg["max_seq_len"],
        prefill_buckets=eng_cfg["prefill_buckets"],
        decode_buckets=eng_cfg["decode_buckets"],
        prefix_cache=eng_cfg["prefix_cache"], chunked_prefill=0,
        speculative=0)


def warm_engine(eng, cfg, eng_cfg) -> None:
    WARMED.append(eng_cfg)
    system.warm_engine(eng, cfg, eng_cfg)
