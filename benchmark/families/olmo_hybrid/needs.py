"""Operations and bytes the Olmo-Hybrid decoder needs, from shapes alone
(``cfg`` is a configuration file's dict).

Every count is of *needed* work: a token at its context counts the matrix
products of every layer and the head, attention over its context in each full
layer, and the recurrence's update in each linear layer (about ``7 d_k d_v`` a
head: the decay, ``S^T k``, the update and ``S^T q``, each a product and a sum
a state entry); a decode step reads every weight once, each row's keys and
values in each full layer up to its context, and each row's state in each
linear layer once and writes it once (float32), with the convolution's tail.
Padding up to a bucket and rows that hold no sequence do not count.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmark.lib.flops import BF16

from . import weights as W

F32 = 4


def _n(cfg, kind: str) -> int:
    """Layers of ``kind`` in the configuration."""
    return sum(1 for k in W.kinds(cfg) if k == kind)


def head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mlp_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def full_params(cfg) -> int:
    """The matrices of a full layer (norm gains apart)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    nh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * h * nh * d + 2 * h * kh * d + mlp_params(cfg)


def linear_params(cfg) -> int:
    """The matrices of a linear layer (norm gains, the convolution, a_log
    and dt_bias apart)."""
    h = cfg["hidden_size"]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return h * (2 * nk * dk + 2 * nv * dv + 2 * nv) + nv * dv * h \
        + mlp_params(cfg)


def conv_width(cfg) -> int:
    return 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] \
        + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def n_params(cfg) -> int:
    """All parameters held (for memory, not for FLOPs)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh = cfg["num_attention_heads"]
    kh = cfg["num_key_value_heads"]
    d = head_dim(cfg)
    full = full_params(cfg) + 2 * h + nh * d + kh * d
    lin = linear_params(cfg) + 2 * h \
        + cfg["linear_conv_kernel_dim"] * conv_width(cfg) \
        + 2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"]
    return _n(cfg, W.FULL) * full + _n(cfg, W.LINEAR) * lin + 2 * h * v + h


def matmul_params(cfg) -> int:
    """Weights that multiply every token, and the head (the embedding is a
    look-up)."""
    return _n(cfg, W.FULL) * full_params(cfg) \
        + _n(cfg, W.LINEAR) * linear_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops_per_key(cfg) -> int:
    """One query position over one key in a full layer, all heads: a score
    and a value sum over ``head_dim`` a head."""
    return 4 * head_dim(cfg) * cfg["num_attention_heads"]


def state_flops_per_token(cfg) -> int:
    """The recurrence's update of one token in one linear layer (the
    convolution's ``2 K`` a channel beside it)."""
    return 7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] \
        + 2 * cfg["linear_conv_kernel_dim"] * conv_width(cfg)


def state_bytes(cfg) -> int:
    """One sequence's state in one linear layer as stored (float32)."""
    return F32 * cfg["linear_num_value_heads"] \
        * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def tail_bytes(cfg) -> int:
    """One sequence's convolution tail in one linear layer (bf16)."""
    return BF16 * (cfg["linear_conv_kernel_dim"] - 1) * conv_width(cfg)


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token in one full layer, as stored."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16


def token_flops(cfg, ctx: int) -> float:
    """One token at context ``ctx`` (keys attended, its own included)."""
    return 2 * matmul_params(cfg) \
        + _n(cfg, W.FULL) * attn_flops_per_key(cfg) * ctx \
        + _n(cfg, W.LINEAR) * state_flops_per_token(cfg)


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (3x forward) FLOPs per trained token; no cell
    trains this family, the count is what one would need."""
    return 3.0 * token_flops(cfg, (seq + 1) / 2.0)


def serve_flops(cfg, prompt_tokens: Iterable[Tuple[int, int]],
                decode_ctx: Iterable[int]) -> float:
    """FLOPs needed for the tokens a serving window processed:
    ``prompt_tokens`` (new, cached_before) per prefill, ``decode_ctx`` the
    context (keys attended, own token included) of every decoded token."""
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    total = 0.0
    for new, before in prompt_tokens:
        keys = new * before + new * (new + 1) // 2
        total += new * (2 * matmul_params(cfg) - head
                        + _n(cfg, W.LINEAR) * state_flops_per_token(cfg)) \
            + head + _n(cfg, W.FULL) * attn_flops_per_key(cfg) * keys
    for ctx in decode_ctx:
        total += token_flops(cfg, ctx)
    return total


def weight_bytes(cfg) -> int:
    """Bytes of every weight a step must read once (bf16; ``a_log`` and
    ``dt_bias`` in float32): all but the embedding table, of which a step
    reads a row a token."""
    nv = cfg["linear_num_value_heads"]
    return BF16 * (n_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]) \
        + _n(cfg, W.LINEAR) * 2 * nv * (F32 - BF16)


def decode_step_needs(cfg, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over rows at contexts ``ctx_lens``:
    every weight once; in each full layer each row's keys and values up to
    its context; in each linear layer each row's state read and written once
    and its convolution tail read and written once."""
    ctx = list(ctx_lens)
    kv = sum(ctx) * _n(cfg, W.FULL) * kv_bytes_per_token(cfg)
    state = len(ctx) * _n(cfg, W.LINEAR) * 2 * (state_bytes(cfg)
                                                + tail_bytes(cfg))
    flops = sum(token_flops(cfg, c) for c in ctx)
    return float(flops), float(weight_bytes(cfg) + kv + state)


def gdn_decode_call_needs(cfg, rows: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE call of the state kernel (one linear layer) over
    ``rows`` rows: each row's state read once and written once (float32),
    its ``q``, ``k``, ``v``, ``g`` and ``beta`` in and ``o`` out (float32);
    ``7 d_k d_v`` FLOPs a head a row."""
    nv, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    io = F32 * nv * (2 * dk + 2 * dv + 2)
    return (float(rows * 7 * nv * dk * dv),
            float(rows * (2 * state_bytes(cfg) + io)))
