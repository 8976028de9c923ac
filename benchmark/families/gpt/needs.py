"""Operations and bytes the GPT-3 decoder needs, from shapes alone.

Every count here is of *needed* work: recomputed activations, padding up to a
bucket and pages gathered beyond a row's real context do not count, so a
share built on these cannot be raised by doing more work than needed.
``cfg`` is a configuration file's dict (``benchmark/configs/*.json``).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmark.lib.flops import BF16


def matmul_params(cfg) -> int:
    """Weights that take part in a matrix product for every token: per layer
    QKV (3h^2), attention out (h^2), MLP up and down (2*h*ffn); the tied
    head (V*h). Biases, layer norms and the two embedding look-ups do not
    multiply."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * f
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * h


def n_params(cfg) -> int:
    """All parameters (for memory, not for FLOPs)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = (4 * h * h + 2 * h * f) + (3 * h + h + f + h) + 4 * h
    return (cfg["num_layers"] * per_layer + cfg["vocab_size"] * h
            + cfg["max_position_embeddings"] * h + 2 * h)


def attn_flops_causal(cfg, ctx_q: int, ctx_k_before: int = 0) -> int:
    """Forward FLOPs of attention in ONE layer for ``ctx_q`` new tokens that
    follow ``ctx_k_before`` cached ones: token i attends i+1+before keys, two
    products (QK^T and PV) of 2*h FLOPs a key. The masked half of the square
    is not counted."""
    h = cfg["hidden_size"]
    keys = ctx_q * ctx_k_before + ctx_q * (ctx_q + 1) // 2
    return 4 * h * keys


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (3x forward; recompute not counted) FLOPs per
    trained token at sequence length ``seq``."""
    fwd_mm = 2 * matmul_params(cfg)
    fwd_attn = cfg["num_layers"] * attn_flops_causal(cfg, seq) / seq
    return 3.0 * (fwd_mm + fwd_attn)


def serve_flops(cfg, prompt_tokens: Iterable[Tuple[int, int]],
                decode_ctx: Iterable[int]) -> float:
    """FLOPs needed for the tokens a serving window processed.

    ``prompt_tokens``: (new, cached_before) per prefill executed;
    ``decode_ctx``: context length (keys attended, the new token's own
    included) of every decode token produced."""
    mm = 2 * matmul_params(cfg)
    L, h = cfg["num_layers"], cfg["hidden_size"]
    total = 0.0
    for new, before in prompt_tokens:
        # the head runs for the last position only; charge it once
        total += new * (mm - 2 * cfg["vocab_size"] * h) \
            + 2 * cfg["vocab_size"] * h \
            + L * attn_flops_causal(cfg, new, before)
    for ctx in decode_ctx:
        total += mm + L * 4 * h * ctx
    return total


def weight_bytes(cfg) -> int:
    """Bytes of every weight a decode step must read once (served in bf16);
    of the position table one row a sequence, which is nothing."""
    return BF16 * (n_params(cfg) - cfg["max_position_embeddings"]
                   * cfg["hidden_size"])


def decode_step_needs(cfg, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for rows whose contexts (keys
    attended, own token included) are ``ctx_lens``: the weights once, and K
    and V of each row's REAL context in every layer; not ``max_seq_len``."""
    L, h = cfg["num_layers"], cfg["hidden_size"]
    ctx = list(ctx_lens)
    kv_bytes = sum(ctx) * L * 2 * h * BF16
    flops = len(ctx) * 2 * matmul_params(cfg) + sum(ctx) * L * 4 * h
    return float(flops), float(weight_bytes(cfg) + kv_bytes)
