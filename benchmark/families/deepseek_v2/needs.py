"""Operations and bytes one chip's share of the DeepSeek-V2 decoder needs,
from shapes alone (``cfg`` is a configuration file's dict).

Every count is of *needed* work: padding up to a bucket, pages read beyond a
row's context, a prefill's query and key heads padded for the flash kernel and
tokens multiplied by an expert they were not routed to do not count. The
routed experts are counted at the EXPECTED load of the share: a token picks
``num_experts_per_tok`` of ``router_width`` experts, of which
``n_routed_experts`` are held here, so ``6 * 10 / 160 = 0.375`` expert
products a token a layer at the benchmark's cut (uniform routing; the
measured load is ``moe.held_assignments_per_token``).

Attention is counted in the form each phase computes: prefill the plain form
(scores over ``nope + rope`` = 192, values over 128, a head), decode the
absorbed form over the latent row (scores over ``kv_lora_rank + rope`` = 576,
values over 512, a head), which is what lets a key be read as 576 values and
not up-projected again at every step.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmark.lib.flops import BF16

from . import weights as W


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"])


def latent_width(cfg) -> int:
    """Values of one token's page row: ``[c_kv | k_rope]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attn_params(cfg) -> int:
    h, nh, nope, rope, dv, kvr, qr = _dims(cfg)
    return (h * qr + qr * nh * (nope + rope) + h * (kvr + rope)
            + kvr * nh * (nope + dv) + nh * dv * h)


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg) -> float:
    """Expected routed-expert products a token a layer on this chip."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / W.router_width(cfg))


def layer_matmul_params(cfg, i: int, routed: float) -> float:
    """Weights one token is multiplied by in layer ``i``, ``routed`` held
    experts of them."""
    h = cfg["hidden_size"]
    if not W.is_moe_layer(cfg, i):
        return attn_params(cfg) + 3 * h * cfg["intermediate_size"]
    return (attn_params(cfg) + h * W.router_width(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg)
            + routed * expert_params(cfg))


def matmul_params(cfg) -> float:
    """Weights that multiply every token, the routed experts at their
    expected share, and the head (the embedding is a look-up)."""
    return (sum(layer_matmul_params(cfg, i, held_share(cfg))
                for i in range(cfg["num_hidden_layers"]))
            + cfg["hidden_size"] * cfg["vocab_size"])


def n_params(cfg) -> int:
    """All parameters held (for memory, not for FLOPs)."""
    h, n = cfg["hidden_size"], 0
    for i in range(cfg["num_hidden_layers"]):
        n += int(layer_matmul_params(cfg, i, cfg["n_routed_experts"]))
        n += 2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"]   # the gains
    return n + 2 * h * cfg["vocab_size"] + h


def prefill_attn_flops(cfg, ctx_q: int, ctx_k_before: int = 0) -> int:
    """Plain-form attention of ONE layer for ``ctx_q`` new tokens after
    ``ctx_k_before`` cached ones; the masked half is not counted."""
    _, nh, nope, rope, dv, _, _ = _dims(cfg)
    keys = ctx_q * ctx_k_before + ctx_q * (ctx_q + 1) // 2
    return 2 * (nope + rope + dv) * nh * keys


def absorbed_flops_per_key(cfg) -> int:
    """Absorbed-form attention of one query over one latent row, all heads:
    a score over 576 and a value sum over 512, a head."""
    return 2 * (latent_width(cfg) + cfg["kv_lora_rank"]) \
        * cfg["num_attention_heads"]


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (3x forward) FLOPs per trained token of the
    share; no cell trains this family, the count is what one would need."""
    fwd_attn = cfg["num_hidden_layers"] * prefill_attn_flops(cfg, seq) / seq
    return 3.0 * (2 * matmul_params(cfg) + fwd_attn)


def serve_flops(cfg, prompt_tokens: Iterable[Tuple[int, int]],
                decode_ctx: Iterable[int]) -> float:
    """FLOPs needed for the tokens a serving window processed:
    ``prompt_tokens`` (new, cached_before) per prefill, ``decode_ctx`` the
    context (keys attended, own token included) of every decode token."""
    mm = 2 * matmul_params(cfg)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    total = 0.0
    for new, before in prompt_tokens:
        # the head runs for the last position only; charge it once
        total += new * (mm - head) + head \
            + L * prefill_attn_flops(cfg, new, before)
    per_key = L * absorbed_flops_per_key(cfg)
    for ctx in decode_ctx:
        total += mm + per_key * ctx
    return total


def weight_bytes(cfg) -> int:
    """Bytes of every weight a decode step must read once (bf16): all that
    is held but the embedding table, of which a step reads a row a
    sequence. Every held expert counts: at the cell's 9.6 tokens an expert
    a step none is idle."""
    return BF16 * (n_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"])


def decode_step_needs(cfg, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step for rows whose contexts are
    ``ctx_lens``: every weight held once and each row's latent rows once in
    every layer; the matrix products at the expected routed share and the
    absorbed attention over each row's REAL context."""
    ctx = list(ctx_lens)
    L = cfg["num_hidden_layers"]
    latent = sum(ctx) * L * latent_width(cfg) * BF16
    flops = len(ctx) * 2 * matmul_params(cfg) \
        + sum(ctx) * L * absorbed_flops_per_key(cfg)
    return float(flops), float(weight_bytes(cfg) + latent)


def mla_decode_call_needs(cfg, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE call of the absorbed latent decode kernel (one
    layer): each row's latent rows read once, its absorbed queries
    (``heads x 576``) read and its latent outputs (``heads x 512``)
    written; a score and a value sum a key a head."""
    ctx = list(ctx_lens)
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    io = len(ctx) * nh * (latent_width(cfg) + kvr) * BF16
    return (float(sum(ctx) * absorbed_flops_per_key(cfg)),
            float(sum(ctx) * latent_width(cfg) * BF16 + io))
