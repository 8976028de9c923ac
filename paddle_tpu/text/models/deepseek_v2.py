"""DeepSeek-V2 (DeepSeek-AI 2024, arXiv:2405.04434): multi-head latent
attention (MLA) and DeepSeekMoE, as one rank of an expert-parallel group
holds it.

A decoder of pre-RMSNorm blocks. Attention compresses the keys and values
of a token into one latent ``c_kv`` (``kv_lora_rank`` wide) plus one rotary
key ``k_rope`` shared by all heads; the queries go through a low-rank
bottleneck too. What a server caches for a token is the row ``[c_kv (after
its norm) | k_rope (after RoPE)]``, 576 values at the published widths
(stored as 640: whole lane tiles, ``DeepseekV2Config.latent_row``), and no
keys or values a head. Prefill computes the plain form (up-project ``c_kv``
to every head's ``k_nope`` and ``v``, flash attention); a decode step the
absorbed form: ``W_UK`` is folded into the query and ``W_UV`` applied after
the weighted sum of latents, so a cached token is read as its row and never
up-projected again. RoPE is YaRN-scaled on the ``qk_rope_head_dim``
dimensions, pairs interleaved in the projections' outputs as the published
code lays them out.

The first ``first_k_dense_replace`` layers have a dense SwiGLU; the others
``n_shared_experts`` shared experts (one SwiGLU of their summed width) plus
routed experts: a float32 softmax router over ALL ``n_routed_experts``,
group-limited greedy top-k, weights ``routed_scaling_factor * p`` (not
renormalised). ``experts_held = (first, count)`` is this rank's share: the
layer computes, droplessly, the pairs routed to the experts it holds
(``incubate/distributed/models/moe/dropless.py``) and leaves out what the
others would add.

The model serves through :class:`~paddle_tpu.serving.ServingEngine` by the
``serve_*`` methods (the engine's seam: see its docstring).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...nn import initializer as I
from ...nn.layer import ParamAttr
from ...incubate.distributed.models.moe.dropless import (
    dropless_glu_experts, group_limited_topk, record_held_pairs)
from ...ops.flash_attention import (flash_attention, latent_attention,
                                    latent_paged_attention)

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "deepseek_v2_tiny",
           "yarn_inv_freq", "yarn_mscale"]

_YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
         "mscale": 0.707, "mscale_all_dim": 0.707,
         "original_max_position_embeddings": 4096}


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 2
    n_routed_experts: int = 160        # the router's width, whatever is held
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict] = field(default_factory=lambda: dict(_YARN))
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    # this rank's share of the routed experts: (first id, count); None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"
    # False: parameters are created as zeros, for a model whose weights are
    # loaded next (drawing 2.2 B normals that are overwritten at once took
    # most of a minute of the serving cell's set-up on the chip)
    init_weights: bool = True

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a cached token's page row holds: the latent and the rotary
        key, zero-padded to whole lane tiles of 128 (576 -> 640 at the
        published widths). The TPU lays a row out in tiles of 128 lanes
        whatever its logical width, so the pad costs no byte the device
        would not spend anyway, and it lets a page move by one aligned
        DMA."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale


def deepseek_v2_tiny(**overrides) -> DeepseekV2Config:
    """A CPU-test preset: every mechanism, no published width."""
    return DeepseekV2Config(**{**dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32, n_shared_experts=2,
        n_routed_experts=16, num_experts_per_tok=3, n_group=4, topk_group=2,
        max_position_embeddings=512,
        rope_scaling=dict(_YARN, original_max_position_embeddings=64)),
        **overrides})


# -- YaRN rotary embedding ---------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, scaling: Optional[Dict]):
    """Inverse frequencies of the ``dim // 2`` rotary pairs: plain RoPE, or
    YaRN's blend of the extrapolated (unscaled) and interpolated (``/
    factor``) frequencies by a linear ramp between the correction
    dimensions of ``beta_fast`` and ``beta_slow`` rotations over the
    original context."""
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return freq.astype(np.float32)
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (freq / scaling["factor"] * (1.0 - mask)
            + freq * mask).astype(np.float32)


def _apply_rope(x, pos, inv_freq, cos_sin_scale: float):
    """Rotate ``x [B, S, ..., dim]`` at positions ``pos [B, S]``. The
    projection lays a rotary pair out as neighbours (2j, 2j+1); they are
    de-interleaved to halves and rotated as halves, as the published code
    does. float32 inside, ``x``'s dtype out."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], axis=-1)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * cos_sin_scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * cos_sin_scale
    half = xf.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(dt)


def _init(cfg) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)
                     if cfg.init_weights else I.Constant(0.0))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False, dtype=cfg.dtype,
                     weight_attr=_init(cfg))


# -- layers --------------------------------------------------------------------

class DeepseekV2Attention(nn.Layer):
    """Multi-head latent attention."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        self.q_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _linear(cfg, h, cfg.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps,
                                        dtype=cfg.dtype)
        self.q_b_proj = _linear(cfg, cfg.q_lora_rank, nh * self.q_head)
        self.kv_a_proj_with_mqa = _linear(cfg, h, cfg.latent_width)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps,
                                         dtype=cfg.dtype)
        self.kv_b_proj = _linear(
            cfg, cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim
                                         + cfg.v_head_dim))
        self.o_proj = _linear(cfg, nh * cfg.v_head_dim, h)
        self.inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                      cfg.rope_scaling)
        # the published code scales cos and sin by mscale / mscale_all_dim
        rs = cfg.rope_scaling
        self.cos_sin_scale = 1.0 if not rs else (
            yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
            / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))

    def project(self, x, pos):
        """``x [B, S, h]`` (normed), ``pos [B, S]`` -> the queries
        ``(q_nope [B, S, H, nope], q_rope [B, S, H, rope])`` and the token's
        page row ``[B, S, latent_row]``: ``[c_kv (after its norm) | k_rope
        (after RoPE) | zeros up to whole lane tiles]``."""
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        q = q.reshape(b, s, cfg.num_attention_heads, self.q_head)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = _apply_rope(q[..., cfg.qk_nope_head_dim:], pos,
                             self.inv_freq, self.cos_sin_scale)
        ckv = self.kv_a_proj_with_mqa(x)
        c_kv = self.kv_a_layernorm(ckv[..., :cfg.kv_lora_rank])
        k_rope = _apply_rope(ckv[..., cfg.kv_lora_rank:], pos,
                             self.inv_freq, self.cos_sin_scale)
        pad = jnp.zeros(c_kv.shape[:-1] + (cfg.latent_row
                                           - cfg.latent_width,), c_kv.dtype)
        return (q_nope, q_rope), jnp.concatenate([c_kv, k_rope, pad],
                                                 axis=-1)

    def _kv_b(self):
        """``kv_b_proj`` a head: ``(W_UK [H, nope, r], W_UV [H, r, v])``."""
        cfg = self.cfg
        w = self.kv_b_proj.weight.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return (jnp.transpose(w[..., :cfg.qk_nope_head_dim], (1, 2, 0)),
                jnp.transpose(w[..., cfg.qk_nope_head_dim:], (1, 0, 2)))

    def attend_plain(self, q, row):
        """Causal self-attention of a whole prompt, plain form: every head's
        ``k_nope`` and ``v`` up-projected from the latent. The flash
        kernel's forward takes values of another head size than the keys;
        each is zero-padded to a size it tiles (q and k from ``nope + rope``
        = 192 to 256 at the published widths, v stays 128): zeros change
        neither a score nor a value."""
        cfg = self.cfg
        q_nope, q_rope = q
        b, s, nh, _ = q_nope.shape
        c_kv = row[..., :cfg.kv_lora_rank]
        k_rope = row[..., cfg.kv_lora_rank:cfg.latent_width]
        kv = self.kv_b_proj(c_kv).reshape(
            b, s, nh, cfg.qk_nope_head_dim + cfg.v_head_dim)
        k_nope, v = kv[..., :cfg.qk_nope_head_dim], \
            kv[..., cfg.qk_nope_head_dim:]
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, nh, cfg.qk_rope_head_dim))],
            axis=-1)
        def pad(a):
            d = _flash_head(a.shape[-1])
            return jnp.pad(a, ((0, 0),) * 3 + ((0, d - a.shape[-1]),))

        o = flash_attention(pad(qq), pad(kk), pad(v), causal=True,
                            scale=cfg.softmax_scale, training=False)
        return o[..., :cfg.v_head_dim]

    def absorb(self, q):
        """The absorbed query ``[q_nope W_UK^T | q_rope | 0]`` ``[B, S, H,
        latent_row]``, as wide as a page row."""
        cfg = self.cfg
        q_nope, q_rope = q
        with jax.named_scope("mla/absorb_q"):
            w_uk, _ = self._kv_b()
            q_lat = jnp.einsum("bshn,hnr->bshr", q_nope, w_uk)
            pad = jnp.zeros(q_lat.shape[:-1] + (cfg.latent_row
                                                - cfg.latent_width,),
                            q_lat.dtype)
            return jnp.concatenate([q_lat, q_rope, pad], axis=-1)

    def up_v(self, o_lat):
        """``o_lat [B, S, H, kv_lora_rank]`` -> ``[B, S, H, v]``."""
        with jax.named_scope("mla/up_v"):
            _, w_uv = self._kv_b()
            return jnp.einsum("bshr,hrv->bshv", o_lat, w_uv)

    def forward(self, x, pos):
        q, row = self.project(x, pos)
        o = self.attend_plain(q, row)
        return self.o_proj(o.reshape(x.shape[0], x.shape[1], -1))


def _flash_head(d: int) -> int:
    """The smallest head size the flash kernel tiles that holds ``d``."""
    for size in (64, 128, 256):
        if d <= size:
            return size
    return d


class DeepseekV2MLP(nn.Layer):
    """SwiGLU."""

    def __init__(self, cfg: DeepseekV2Config, width: int):
        super().__init__()
        self.gate_proj = _linear(cfg, cfg.hidden_size, width)
        self.up_proj = _linear(cfg, cfg.hidden_size, width)
        self.down_proj = _linear(cfg, width, cfg.hidden_size)

    def forward(self, x):
        return self.down_proj(jax.nn.silu(self.gate_proj(x))
                              * self.up_proj(x))


class DeepseekV2MoE(nn.Layer):
    """Shared experts plus this rank's share of the routed experts."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        self.first, self.count = cfg.held
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        init = _init(cfg)
        self.router = _linear(cfg, h, cfg.n_routed_experts)
        self.shared_experts = DeepseekV2MLP(cfg, f * cfg.n_shared_experts)
        self.w_gate = self.create_parameter((self.count, h, f), attr=init,
                                            dtype=cfg.dtype)
        self.w_up = self.create_parameter((self.count, h, f), attr=init,
                                          dtype=cfg.dtype)
        self.w_down = self.create_parameter((self.count, f, h), attr=init,
                                            dtype=cfg.dtype)

    def route(self, x):
        """``x [T, h]`` -> ``(idx [T, k], weight [T, k])``: float32 softmax
        over every routed expert, group-limited greedy top-k, weights scaled
        and (as published for this model) not renormalised."""
        cfg = self.cfg
        with jax.named_scope("moe/route"):
            logits = jnp.matmul(
                x.astype(jnp.float32),
                self.router.weight.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            probs = jax.nn.softmax(logits, axis=-1)
            idx, weight = group_limited_topk(
                probs, cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group)
            if cfg.norm_topk_prob and cfg.num_experts_per_tok > 1:
                weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
            return idx, weight * cfg.routed_scaling_factor

    def forward(self, x, real=None):
        """``x [B, S, h]`` -> ``(y, load [count] int32)``; ``real [B, S]``
        masks tokens that are padding out of ``load`` (their outputs are
        computed and discarded by the caller as ever)."""
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        idx, weight = self.route(flat)
        if real is not None:
            # a padded token is routed nowhere: its pairs cost nothing
            idx = jnp.where(real.reshape(-1, 1), idx, -1)
        with jax.named_scope("moe/experts"):
            y, load = dropless_glu_experts(
                flat, idx, weight, self.w_gate, self.w_up, self.w_down,
                first=self.first)
        with jax.named_scope("moe/shared"):
            y = y.astype(x.dtype) + self.shared_experts(flat)
        return y.reshape(b, s, h), load


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.is_moe = index >= cfg.first_k_dense_replace
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = DeepseekV2Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.mlp = DeepseekV2MoE(cfg) if self.is_moe \
            else DeepseekV2MLP(cfg, cfg.intermediate_size)

    # -- the serving engine's layer step -----------------------------------

    def serve_project(self, x, pos):
        q, row = self.self_attn.project(self.input_layernorm(x), pos)
        return q, (row,)

    def serve_attend_prefill(self, q, rows):
        return self.self_attn.attend_plain(q, rows[0])

    def serve_attend_paged(self, q, pools, tables, lengths, block_size,
                           layer):
        attn, cfg = self.self_attn, self.cfg
        q_abs = attn.absorb(q)
        with jax.named_scope("mla/attend"):
            o_lat = latent_paged_attention(
                q_abs, pools[0], tables, lengths, block_size=block_size,
                value_dim=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                layer=layer)
        return attn.up_v(o_lat)

    def serve_attend_extend(self, q, pools, tables, pos, block_size, layer):
        attn, cfg = self.self_attn, self.cfg
        b = tables.shape[0]
        rows = pools[0][layer][tables].reshape(
            b, tables.shape[1] * block_size, cfg.latent_row)
        with jax.named_scope("mla/attend"):
            o_lat = latent_attention(attn.absorb(q), rows, pos,
                                     value_dim=cfg.kv_lora_rank,
                                     scale=cfg.softmax_scale)
        return attn.up_v(o_lat)

    def serve_finish(self, x, o, real):
        b, s = x.shape[:2]
        x = x + self.self_attn.o_proj(o.reshape(b, s, -1))
        y = self.post_attention_layernorm(x)
        if not self.is_moe:
            return x + self.mlp(y), None
        out, load = self.mlp(y, real)
        return x + out, load

    def forward(self, x, pos):
        o = self.self_attn.attend_plain(
            *self.self_attn.project(self.input_layernorm(x), pos))
        return self.serve_finish(x, o, None)[0]


class DeepseekV2Model(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_init(cfg))
        self.layers = nn.LayerList([DeepseekV2DecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, pos)
        return self.norm(x)


class DeepseekV2ForCausalLM(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV2Model(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        """Full causal forward, plain attention: ``[B, S] -> [B, S, V]``."""
        return self.logits(self.model(input_ids))

    # -- the serving engine's seam (serving/engine.py) ---------------------

    serve_generation = None             # a token a row a step

    #: the decode and prefill programs return, beside the token, the pairs
    #: each held expert got (summed over the expert layers)
    @property
    def serve_counts(self) -> int:
        return self.cfg.held[1] if any(l.is_moe for l in self.model.layers) \
            else 0

    def serve_cache_rows(self):
        return ((self.cfg.latent_row,),)

    def serve_dtype(self):
        return self.model.embed_tokens.weight.dtype

    def serve_layers(self):
        return list(self.model.layers)

    def serve_embed(self, ids, pos):
        return self.model.embed_tokens(ids)

    def serve_final_norm(self, x):
        return self.model.norm(x)

    @property
    def serve_latent_value_dim(self) -> int:
        """The pool is latent: a row's value is its first ``kv_lora_rank``
        entries (what the engine hands ``takes_paged_kernel``)."""
        return self.cfg.kv_lora_rank

    def serve_record_counts(self, load: np.ndarray, n_tokens: int) -> None:
        """The counters behind the programs' counts: ``n_tokens`` real tokens
        went through every expert layer, ``load[e]`` of their pairs fell to
        held expert ``e``."""
        cfg = self.cfg
        record_held_pairs(
            load, n_tokens, top_k=cfg.num_experts_per_tok,
            n_layers=sum(1 for l in self.model.layers if l.is_moe),
            first=cfg.held[0])
