"""What the grouped-query decoder needs: the KV projection and the cached K
and V are ``2 * num_kv_heads * head_dim`` wide, not ``2 * hidden``. A
fixture."""

BF16 = 2


def _kv(cfg) -> int:
    return cfg["num_kv_heads"] * cfg["head_dim"]


def matmul_params(cfg) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 2 * h * h + 2 * h * _kv(cfg) + 2 * h * f
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * h


def n_params(cfg) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    biases_and_norms = (h + 2 * _kv(cfg) + h + f + h) + 4 * h
    return (matmul_params(cfg) + cfg["num_layers"] * biases_and_norms
            + cfg["max_position_embeddings"] * h + 2 * h)


def _attn_keys(new: int, before: int = 0) -> int:
    return new * before + new * (new + 1) // 2


def train_flops_per_token(cfg, seq: int) -> float:
    attn = cfg["num_layers"] * 4 * cfg["hidden_size"] * _attn_keys(seq) / seq
    return 3.0 * (2 * matmul_params(cfg) + attn)


def serve_flops(cfg, prompt_tokens, decode_ctx) -> float:
    L, h, head = cfg["num_layers"], cfg["hidden_size"], \
        2 * cfg["vocab_size"] * cfg["hidden_size"]
    mm = 2 * matmul_params(cfg)
    total = 0.0
    for new, before in prompt_tokens:
        total += new * (mm - head) + head + L * 4 * h * _attn_keys(new, before)
    for ctx in decode_ctx:
        total += mm + L * 4 * h * ctx
    return total


def weight_bytes(cfg) -> int:
    return BF16 * (n_params(cfg)
                   - cfg["max_position_embeddings"] * cfg["hidden_size"])


def decode_step_needs(cfg, ctx_lens):
    ctx = list(ctx_lens)
    keys = sum(ctx) * cfg["num_layers"]
    return (keys * 4.0 * cfg["hidden_size"]
            + len(ctx) * 2.0 * matmul_params(cfg),
            keys * 2.0 * _kv(cfg) * BF16 + weight_bytes(cfg))
