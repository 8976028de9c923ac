"""Operations and bytes one chip's share of the SDAR-MoE decoder needs, from
shapes alone (``cfg`` is a configuration file's dict).

Every count is of *needed* work, and for generation by diffusion that is what
ONE forward of a token needs: a token that became visible in a step, at its
context, counts the matrix products at the expected routed share and attention
over its context; every held weight is read once a step, and the keys and
values of a row once for the BLOCK that became visible (one read serves all its
positions, as the kernel does it), not once a token. The passes a block really
takes (four denoise passes and a commit at the published four steps, at least
one denoise pass and a commit where the confidence threshold unmasks a whole
block at once) cost several times that, so a share built on these counts
(``serve.step_mfu``, ``kernels.decode_step_roofline``) charges the extra passes
to the program and moves when a later change saves a pass; it stays under
100 % on either branch of the unmask rule, because a pass reads every row's
keys and values and a step counts only the rows whose block it made visible.
Padding up to a bucket, pages read beyond a row's context and tokens multiplied
by an expert they were not routed to do not count either.

The routed experts are counted at the EXPECTED load of the share: a token
picks ``num_experts_per_tok`` of ``router_width`` experts, of which
``num_experts`` are held here: ``8 * 16 / 128 = 1`` expert product a token a
layer at the benchmark's cut (uniform routing; the measured load is
``moe.held_assignments_per_token``).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmark.lib.flops import BF16

from . import weights as W


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def block_length(cfg) -> int:
    return cfg["generation"]["block_length"]


def attn_params(cfg) -> int:
    """q, k, v and o projections of one layer."""
    h, nh, kh, d = _dims(cfg)
    return h * nh * d + 2 * h * kh * d + nh * d * h


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg) -> float:
    """Expected routed-expert products a token a layer on this chip."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / W.router_width(cfg))


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token in one layer, as stored (no padding)."""
    _, _, kh, d = _dims(cfg)
    return 2 * kh * d * BF16


def matmul_params(cfg) -> float:
    """Weights that multiply every token, the routed experts at their
    expected share, and the head (the embedding is a look-up)."""
    h = cfg["hidden_size"]
    per_layer = attn_params(cfg) + h * W.router_width(cfg) \
        + held_share(cfg) * expert_params(cfg)
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def n_params(cfg) -> int:
    """All parameters held (for memory, not for FLOPs)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    per_layer = attn_params(cfg) + h * W.router_width(cfg) \
        + cfg["num_experts"] * expert_params(cfg) + 2 * h + 2 * d
    return cfg["num_hidden_layers"] * per_layer \
        + 2 * h * cfg["vocab_size"] + h


def attn_flops_per_key(cfg) -> int:
    """One query position over one key, all query heads: a score and a
    value sum over ``head_dim`` a head."""
    _, nh, _, d = _dims(cfg)
    return 4 * d * nh


def prefill_attn_flops(cfg, ctx_q: int, ctx_k_before: int = 0) -> int:
    """Block-causal attention of ONE layer for ``ctx_q`` new tokens after
    ``ctx_k_before`` cached ones: a token sees the earlier tokens and its
    own block whole; what the mask hides is not counted."""
    b = block_length(cfg)
    whole, tail = divmod(ctx_q, b)
    keys = ctx_q * ctx_k_before + b * b * whole * (whole + 1) // 2 \
        + tail * ctx_q
    return attn_flops_per_key(cfg) * keys


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (3x forward) FLOPs per trained token of the
    share; no cell trains this family, the count is what one would need."""
    fwd_attn = cfg["num_hidden_layers"] * prefill_attn_flops(cfg, seq) / seq
    return 3.0 * (2 * matmul_params(cfg) + fwd_attn)


def serve_flops(cfg, prompt_tokens: Iterable[Tuple[int, int]],
                decode_ctx: Iterable[int]) -> float:
    """FLOPs needed for the tokens a serving window processed:
    ``prompt_tokens`` (new, cached_before) per prefill (no head: a prefill
    yields no token), ``decode_ctx`` the context (keys attended, own token
    included) of every token that became visible, each at ONE forward."""
    mm = 2 * matmul_params(cfg)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    total = 0.0
    for new, before in prompt_tokens:
        total += new * (mm - head) + L * prefill_attn_flops(cfg, new, before)
    per_key = L * attn_flops_per_key(cfg)
    for ctx in decode_ctx:
        total += mm + per_key * ctx
    return total


def weight_bytes(cfg) -> int:
    """Bytes of every weight a pass must read once (bf16): all that is held
    but the embedding table, of which a pass reads a row a position. Every
    held expert counts: at the cell's 32 pairs an expert a pass none is
    idle."""
    return BF16 * (n_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"])


def runs_end(ctx_lens: Iterable[int], block: int):
    """The last entry of each run of consecutive contexts, a run cut after
    ``block`` entries: the driver appends a row's gained tokens as a run of
    consecutive contexts and a block's tokens become visible together, so
    each is the keys one row's block attended (its context and itself)."""
    ctx = list(ctx_lens)
    ends, n = [], 0
    for i, c in enumerate(ctx):
        n += 1
        last = i + 1 == len(ctx) or ctx[i + 1] != c + 1
        if last or n == block:
            ends.append(c)
            n = 0
    return ends


def decode_step_needs(cfg, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step for the tokens that became visible in it,
    ``ctx_lens`` their contexts: every weight held once, and in every layer
    the keys and values of each BLOCK that became visible once, up to its
    last token (:func:`runs_end`); the matrix products at the expected routed
    share and attention over each token's context: ONE forward a token,
    whatever the passes took."""
    ctx = list(ctx_lens)
    L = cfg["num_hidden_layers"]
    kv = sum(runs_end(ctx, block_length(cfg))) * L * kv_bytes_per_token(cfg)
    flops = len(ctx) * 2 * matmul_params(cfg) \
        + sum(ctx) * L * attn_flops_per_key(cfg)
    return float(flops), float(weight_bytes(cfg) + kv)


def block_paged_call_needs(cfg, key_counts: Iterable[int]
                           ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE call of the block paged attention kernel (one
    layer) over rows that attend ``key_counts`` keys each (context and the
    block in flight): each row's keys and values read once, its ``B x
    heads`` queries read and as many outputs written; a score and a value
    sum a key a query head a position of the block."""
    keys = list(key_counts)
    _, nh, _, d = _dims(cfg)
    b = block_length(cfg)
    io = len(keys) * 2 * b * nh * d * BF16
    return (float(sum(keys) * b * attn_flops_per_key(cfg)),
            float(sum(keys) * kv_bytes_per_token(cfg) + io))
