"""SDAR-MoE (JetLM, SDAR-30B-A3B-Chat; ``model_type: sdar_moe``): a Qwen3-MoE
style decoder that generates by diffusion over blocks, as one rank of an
expert-parallel group holds it.

A decoder of pre-RMSNorm blocks, no biases. Attention is grouped-query
(``num_attention_heads`` queries over ``num_key_value_heads`` keys and values
of ``head_dim``), with an RMSNorm over each head's ``head_dim`` values of the
queries and of the keys (one gain vector for all heads) before the rotation;
RoPE over the whole head, halves layout (``rotate_half``). Every layer is a
routed-expert layer: a float32 softmax router over ALL ``num_experts``, the
``num_experts_per_tok`` largest probabilities renormalised to sum 1
(``norm_topk_prob``), no shared expert. ``experts_held = (first, count)`` is
this rank's share: the layer computes, droplessly, the pairs routed to the
experts it holds (``incubate/distributed/models/moe/dropless.py``) and leaves
out what the others would add.

**The mask is block-causal**, block length ``block_length`` counted from
position 0: position ``i`` sees position ``j`` iff ``j // B <= i // B``. That
holds for prompt and answer alike, and it is what generation by diffusion
needs: a block of ``B`` positions is denoised together, every position seeing
the whole block over the cache of the earlier ones (``serve_generation``, the
answer to the serving engine's question how this model generates; the engine
runs the passes, ``serving/engine.py``).

The model serves through :class:`~paddle_tpu.serving.ServingEngine` by the
``serve_*`` methods (the engine's seam: see its docstring).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...nn import initializer as I
from ...nn.layer import ParamAttr
from ...incubate.distributed.models.moe.dropless import (
    dropless_glu_experts, record_held_pairs, renormalised_topk)
from ...ops.flash_attention import block_paged_attention, flash_attention

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "sdar_moe_tiny",
           "BlockGeneration"]


class BlockGeneration(NamedTuple):
    """How a model that generates by diffusion over blocks is served: blocks
    of ``block_length`` positions counted from position 0, each denoised in
    passes that unmask at least ``block_length // steps`` positions (every
    masked position whose confidence passes ``threshold`` if those are as
    many, else the most confident), from ``mask_id`` embeddings."""
    block_length: int
    steps: int
    threshold: float
    mask_id: int


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128             # the router's width, whatever is held
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # generation by diffusion over blocks (the release's defaults)
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    # this rank's share of the routed experts: (first id, count); None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"
    # False: parameters are created as zeros, for a model whose weights are
    # loaded next
    init_weights: bool = True

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.num_experts))


def sdar_moe_tiny(**overrides) -> SdarMoeConfig:
    """A CPU-test preset: every mechanism, no published width but the head's
    (128: the page pool then takes the layout it has on the chip)."""
    return SdarMoeConfig(**{**dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
        max_position_embeddings=256, mask_token_id=511), **overrides})


def rope_halves(x, pos, theta: float):
    """Rotate ``x [B, S, heads, dim]`` at positions ``pos [B, S]``, halves
    layout: ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2 |
    x1]`` and the ``dim // 2`` frequencies ``theta^(-2j / dim)`` repeated
    over both halves. float32 inside, ``x``'s dtype out."""
    dt, dim = x.dtype, x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = pos.astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    xf = x.astype(jnp.float32)
    half = dim // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(dt)


def _init(cfg) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)
                     if cfg.init_weights else I.Constant(0.0))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False, dtype=cfg.dtype,
                     weight_attr=_init(cfg))


class SdarMoeAttention(nn.Layer):
    """Grouped-query attention with per-head RMSNorm on queries and keys."""

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        h, nh, kh, d = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = _linear(cfg, h, nh * d)
        self.k_proj = _linear(cfg, h, kh * d)
        self.v_proj = _linear(cfg, h, kh * d)
        self.o_proj = _linear(cfg, nh * d, h)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, dtype=cfg.dtype)

    def project(self, x, pos):
        """``x [B, S, h]`` (normed), ``pos [B, S]`` -> ``(q [B, S, H, D],
        k [B, S, KH, D], v [B, S, KH, D])``, queries and keys normed a head
        and rotated."""
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.q_proj(x).reshape(b, s, cfg.num_attention_heads,
                                   cfg.head_dim)
        k = self.k_proj(x).reshape(b, s, cfg.num_key_value_heads,
                                   cfg.head_dim)
        v = self.v_proj(x).reshape(b, s, cfg.num_key_value_heads,
                                   cfg.head_dim)
        q = rope_halves(self.q_norm(q), pos, cfg.rope_theta)
        k = rope_halves(self.k_norm(k), pos, cfg.rope_theta)
        return q, k, v

    def attend_plain(self, q, k, v=None):
        """Block-causal self-attention of whole blocks (a prompt's clean
        blocks, or a full sequence); ``k`` alone is the fused cache row,
        which flash splits."""
        return flash_attention(q, k, v, causal=True,
                               causal_block=self.cfg.block_length,
                               training=False)


class SdarMoeExperts(nn.Layer):
    """The router and this rank's share of the routed experts."""

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.first, self.count = cfg.held
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        init = _init(cfg)
        self.router = _linear(cfg, h, cfg.num_experts)
        self.w_gate = self.create_parameter((self.count, h, f), attr=init,
                                            dtype=cfg.dtype)
        self.w_up = self.create_parameter((self.count, h, f), attr=init,
                                          dtype=cfg.dtype)
        self.w_down = self.create_parameter((self.count, f, h), attr=init,
                                            dtype=cfg.dtype)

    def route(self, x):
        """``x [T, h]`` -> ``(idx [T, k], weight [T, k])``: float32 softmax
        over every expert, the ``k`` largest, renormalised to sum 1 where the
        configuration says so."""
        cfg = self.cfg
        with jax.named_scope("moe/route"):
            logits = jnp.matmul(
                x.astype(jnp.float32),
                self.router.weight.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            return renormalised_topk(jax.nn.softmax(logits, axis=-1),
                                     cfg.num_experts_per_tok,
                                     cfg.norm_topk_prob)

    def forward(self, x, real=None):
        """``x [B, S, h]`` -> ``(y, load [count] int32)``; ``real [B, S]``
        masks tokens that are padding out of ``load``."""
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
        return y.astype(x.dtype).reshape(b, s, h), load


class SdarMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = SdarMoeAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.mlp = SdarMoeExperts(cfg)

    # -- the serving engine's layer step -----------------------------------

    def serve_project(self, x, pos):
        """The queries and the token's ONE cache row: keys the first ``KH``
        heads, values the rest (``ops/paged_layout.py``, the fused row)."""
        q, k, v = self.self_attn.project(self.input_layernorm(x), pos)
        return q, (jnp.concatenate([k, v], axis=2),)

    def serve_attend_prefill(self, q, rows):
        return self.self_attn.attend_plain(q, *rows)

    def serve_attend_block(self, q, pools, tables, lengths, block_size,
                           layer):
        """A block's queries ``[rows, B, H, D]`` over each row's pages up to
        ``lengths`` (context and the block itself, which the pass has just
        written): within the block nothing is masked."""
        return block_paged_attention(q, *pools, tables, lengths,
                                     block_size=block_size, layer=layer)

    def serve_finish(self, x, o, real):
        b, s = x.shape[:2]
        x = x + self.self_attn.o_proj(o.reshape(b, s, -1))
        out, load = self.mlp(self.post_attention_layernorm(x), real)
        return x + out, load

    def forward(self, x, pos):
        q, rows = self.serve_project(x, pos)
        return self.serve_finish(x, self.serve_attend_prefill(q, rows),
                                 None)[0]


class SdarMoeModel(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_init(cfg))
        self.layers = nn.LayerList([SdarMoeDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, pos)
        return self.norm(x)


class SdarMoeForCausalLM(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SdarMoeModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        """Full block-causal forward: ``[B, S] -> [B, S, V]`` (the logit at
        position ``i`` predicts token ``i``: no shift)."""
        return self.logits(self.model(input_ids))

    # -- the serving engine's seam (serving/engine.py) ---------------------

    serve_latent_value_dim = None       # keys and values, not a latent row

    @property
    def serve_generation(self) -> BlockGeneration:
        cfg = self.cfg
        return BlockGeneration(cfg.block_length, cfg.denoising_steps,
                               cfg.confidence_threshold, cfg.mask_token_id)

    #: the programs return, beside the tokens, the pairs each held expert
    #: got (summed over the layers)
    @property
    def serve_counts(self) -> int:
        return self.cfg.held[1]

    def serve_cache_rows(self):
        """One fused row a token, so one pool: a page's keys and values are
        contiguous and the block kernel fetches them as one descriptor."""
        return ((2 * self.cfg.num_key_value_heads, self.cfg.head_dim),)

    def serve_dtype(self):
        return self.model.embed_tokens.weight.dtype

    def serve_layers(self):
        return list(self.model.layers)

    def serve_embed(self, ids, pos):
        return self.model.embed_tokens(ids)

    def serve_final_norm(self, x):
        return self.model.norm(x)

    def serve_record_counts(self, load: np.ndarray, n_tokens: int) -> None:
        """The counters behind the programs' counts, as DeepSeek-V2's:
        ``n_tokens`` real tokens went through every layer, ``load[e]`` of
        their pairs fell to held expert ``e``."""
        cfg = self.cfg
        record_held_pairs(load, n_tokens, top_k=cfg.num_experts_per_tok,
                          n_layers=cfg.num_hidden_layers, first=cfg.held[0])
