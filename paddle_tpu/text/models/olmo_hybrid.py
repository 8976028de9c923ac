"""Olmo-Hybrid (AllenAI, Olmo-Hybrid-7B; ``model_type: olmo_hybrid``): a
decoder whose layers are of two kinds, in the order ``layer_types`` gives
(``linear_attention`` three times, then ``full_attention``, repeated).

Every block follows the Olmo 3 convention, the norms AFTER the sublayers and
none before::

    h = x + RMSNorm(mixer(x));   out = h + RMSNorm(MLP(h))

with ``MLP`` a SwiGLU, no biases anywhere.

- **A full layer** is multi-head attention (``num_attention_heads`` queries
  over as many keys and values of ``hidden / heads``) with an RMSNorm over
  the whole projection of the queries and of the keys, causal, and no
  positional rotation (NoPE: the published ``rope_theta`` is null). It caches
  a token's keys and values as ONE fused row of ``2 * heads`` (keys first),
  like SDAR (``ops/paged_layout.py``).
- **A linear layer** is the gated delta rule (``ops/gated_delta.py``):
  ``[q; k; v] = x [W_q; W_k; W_v]`` through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps and SiLU; ``q`` and ``k`` L2-normalised a
  head, ``q`` scaled by ``1 / sqrt(d_k)``; ``beta = sigmoid(x W_b)``, times 2
  where ``linear_allow_neg_eigval`` (so ``beta`` lies in (0, 2)); ``g =
  -exp(A_log) softplus(x W_a + dt_bias)``; the recurrence over a state of
  ``[d_k, d_v]`` a head; ``y = W_o(RMSNorm_dv(o) * silu(x W_z))``, the norm
  over each head's ``d_v`` values with one gain for all heads, in float32. It
  keeps, for a sequence, the state and the convolution's last ``K - 1``
  inputs: a fixed size, whatever the sequence's length. The state is float32
  as the published kernels keep it.

The model serves through :class:`~paddle_tpu.serving.ServingEngine` by the
``serve_*`` methods (the engine's seam: see its docstring). A full layer gives
the seam of a layer that caches rows a token; a linear layer says
``serve_keeps = "state"`` and gives ``serve_prefill_state`` and
``serve_decode_state`` instead; the model's ``serve_state()`` gives the shapes
of what such a layer keeps a sequence.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp

from ... import nn
from ...nn import initializer as I
from ...nn.layer import ParamAttr
from ...ops.flash_attention import block_paged_attention, flash_attention
from ...ops.gated_delta import (causal_conv, chunk_gated_delta, conv_step,
                                gated_delta_decode, l2_normalize)

__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM"]

PERIOD = ("linear_attention",) * 3 + ("full_attention",)

#: a slot's convolution tail is kept as rows of this many lanes
LANES = 128


def _tail_rows(n: int) -> int:
    """Rows of :data:`LANES` that hold a tail of ``n`` values."""
    return -(-n // LANES)


def _to_lane_rows(tail):
    """``tail [B, K - 1, C]`` -> ``[B, rows, LANES]``, flat and the last row
    zero-padded."""
    b = tail.shape[0]
    flat = tail.reshape(b, -1)
    n = flat.shape[1]
    flat = jnp.pad(flat, ((0, 0), (0, _tail_rows(n) * LANES - n)))
    return flat.reshape(b, -1, LANES)


def _from_lane_rows(rows, k_taps, width):
    """:func:`_to_lane_rows`' inverse: ``[B, rows, LANES]`` -> ``[B, K - 1,
    C]``, the padding dropped."""
    b = rows.shape[0]
    n = (k_taps - 1) * width
    return rows.reshape(b, -1)[:, :n].reshape(b, k_taps - 1, width)


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    #: the published pattern; the model is its first ``num_hidden_layers``
    layer_types: List[str] = field(default_factory=lambda: list(PERIOD * 8))
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"
    # False: parameters are created as zeros, for a model whose weights are
    # loaded next
    init_weights: bool = True

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kinds(self) -> List[str]:
        return list(self.layer_types[:self.num_hidden_layers])


def _init(cfg) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)
                     if cfg.init_weights else I.Constant(0.0))


def _linear(cfg, n_in, n_out):
    return nn.Linear(n_in, n_out, bias_attr=False, dtype=cfg.dtype,
                     weight_attr=_init(cfg))


class OlmoHybridMLP(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _linear(cfg, h, f)
        self.up_proj = _linear(cfg, h, f)
        self.down_proj = _linear(cfg, f, h)

    def forward(self, x):
        return self.down_proj(jax.nn.silu(self.gate_proj(x))
                              * self.up_proj(x))


class _Block(nn.Layer):
    """The Olmo 3 block around a mixer: the norms after the sublayers."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        eps, h = cfg.rms_norm_eps, cfg.hidden_size
        self.post_attention_layernorm = nn.RMSNorm(h, eps, dtype=cfg.dtype)
        self.mlp = OlmoHybridMLP(cfg)
        self.post_feedforward_layernorm = nn.RMSNorm(h, eps, dtype=cfg.dtype)

    def _rest(self, x, y):
        """``x`` and the mixer's output ``y`` through the rest of the block."""
        x = x + self.post_attention_layernorm(y)
        return x + self.post_feedforward_layernorm(self.mlp(x))


class OlmoHybridAttentionLayer(_Block):
    """A full layer: causal multi-head attention without rotation."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(cfg)
        h, nh, kh, d = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = _linear(cfg, h, nh * d)
        self.k_proj = _linear(cfg, h, kh * d)
        self.v_proj = _linear(cfg, h, kh * d)
        self.o_proj = _linear(cfg, nh * d, h)
        self.q_norm = nn.RMSNorm(nh * d, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.k_norm = nn.RMSNorm(kh * d, cfg.rms_norm_eps, dtype=cfg.dtype)

    # -- the serving engine's layer step -----------------------------------

    def serve_project(self, x, pos):
        """The queries ``[B, S, H, D]`` and the token's ONE cache row, keys
        the first ``KH`` heads and values the rest; no rotation (NoPE)."""
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        q = self.q_norm(self.q_proj(x)).reshape(b, s, -1, d)
        k = self.k_norm(self.k_proj(x)).reshape(b, s, -1, d)
        v = self.v_proj(x).reshape(b, s, -1, d)
        return q, (jnp.concatenate([k, v], axis=2),)

    def serve_attend_prefill(self, q, rows):
        return flash_attention(q, *rows, causal=True, training=False)

    def serve_attend_paged(self, q, pools, tables, lengths, block_size,
                           layer):
        """One query a row over its pages up to ``lengths``: the block paged
        kernel at one position (the fused pool's pages are heads first)."""
        return block_paged_attention(q, *pools, tables, lengths,
                                     block_size=block_size, layer=layer)

    def serve_finish(self, x, o, real):
        b, s = x.shape[:2]
        return self._rest(x, self.o_proj(o.reshape(b, s, -1))), None

    def forward(self, x):
        pos = jnp.zeros(x.shape[:2], jnp.int32)
        q, rows = self.serve_project(x, pos)
        return self.serve_finish(x, self.serve_attend_prefill(q, rows),
                                 None)[0]


class OlmoHybridLinearLayer(_Block):
    """A linear layer: the gated delta rule over a state a sequence."""

    #: what the layer keeps for a sequence (the engine's seam)
    serve_keeps = "state"

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__(cfg)
        h = cfg.hidden_size
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        if nk != nv:
            raise ValueError(f"{nk} key heads over {nv} value heads: the "
                             "layer is written for as many of each")
        self.q_proj = _linear(cfg, h, nk * dk)
        self.k_proj = _linear(cfg, h, nk * dk)
        self.v_proj = _linear(cfg, h, nv * dv)
        self.g_proj = _linear(cfg, h, nv * dv)       # the output gate, z
        self.a_proj = _linear(cfg, h, nv)
        self.b_proj = _linear(cfg, h, nv)
        width = nk * dk * 2 + nv * dv
        self.conv_weight = self.create_parameter(
            (cfg.linear_conv_kernel_dim, width), attr=_init(cfg),
            dtype=cfg.dtype)
        # float32 as the published model keeps them
        self.A_log = self.create_parameter((nv,), attr=_init(cfg),
                                           dtype="float32")
        self.dt_bias = self.create_parameter((nv,), attr=_init(cfg),
                                             dtype="float32")
        self.o_norm = nn.RMSNorm(dv, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.o_proj = _linear(cfg, nv * dv, h)

    def _mix_in(self, x):
        """The convolution's input ``[q; k; v]`` (the projections) and the
        gates: ``(xc, z, g, beta)``, ``g`` and ``beta`` float32 ``[..., H]``."""
        cfg = self.cfg
        xc = jnp.concatenate([self.q_proj(x), self.k_proj(x),
                              self.v_proj(x)], axis=-1)
        beta = jax.nn.sigmoid(self.b_proj(x).astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = beta * 2.0
        g = -jnp.exp(self.A_log.astype(jnp.float32)) * jax.nn.softplus(
            self.a_proj(x).astype(jnp.float32)
            + self.dt_bias.astype(jnp.float32))
        return xc, self.g_proj(x), g, beta

    def _heads(self, conv):
        """The convolution's output split into ``q, k [..., H, d_k]``
        (normalised, ``q`` scaled) and ``v [..., H, d_v]``, float32."""
        cfg = self.cfg
        nk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        lead = conv.shape[:-1]
        q = conv[..., :nk * dk].reshape(lead + (nk, dk))
        k = conv[..., nk * dk:2 * nk * dk].reshape(lead + (nk, dk))
        v = conv[..., 2 * nk * dk:].reshape(
            lead + (-1, cfg.linear_value_head_dim))
        return l2_normalize(q) * dk ** -0.5, l2_normalize(k), v

    def _mix_out(self, x, o, z):
        """``o [..., H, d_v]`` float32 through the gated norm and ``W_o``,
        then the rest of the block."""
        with jax.named_scope("gdn/gate_norm"):
            gate = jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
            y = (self.o_norm(o) * gate).reshape(o.shape[:-2] + (-1,))
        return self._rest(x, self.o_proj(y.astype(x.dtype)))

    # -- the serving engine's layer step -----------------------------------

    def serve_prefill_state(self, x, n_tokens):
        """A prompt ``x [B, S, hidden]`` whose first ``n_tokens`` are real ->
        ``(x, (state [B, d_k, H * d_v] float32, tail [B, rows, 128]))``: the
        state after the real tokens (positions past them have ``g = beta =
        0``, which leave it as it is) and the convolution's inputs of the
        last ``K - 1`` real tokens (zeros before position 0), in the rows of
        lanes ``serve_state()`` gives."""
        k_taps = self.cfg.linear_conv_kernel_dim
        xc, z, g, beta = self._mix_in(x)
        real = (jnp.arange(x.shape[1]) < n_tokens)[None, :, None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        with jax.named_scope("gdn/conv"):
            q, k, v = self._heads(causal_conv(xc, self.conv_weight))
            padded = jnp.pad(xc, ((0, 0), (k_taps - 1, 0), (0, 0)))
            tail = jax.lax.dynamic_slice_in_dim(padded, n_tokens, k_taps - 1,
                                                axis=1)
        with jax.named_scope("gdn/chunk"):
            o, state = chunk_gated_delta(q, k, v, g, beta)
        return self._mix_out(x, o, z), (state, _to_lane_rows(tail))

    def serve_decode_state(self, x, pools, slots, layer):
        """One token a row ``x [B, 1, hidden]`` over the pools of what the
        state layers keep (``[state layers, slots, ...]``, ``serve_state()``),
        each row's by ``slots [B]`` (0: a pad row) in ``layer`` (this layer's
        place among the state layers) -> ``(x, pools)``, the row's state and
        tail advanced in place. A slot's tail is whole ``(16, 128)`` tiles of
        its pool, so the write by slot is one scatter of whole tiles (a flat
        tail is one sublane of each tile, and its write a loop over the
        rows)."""
        state_pool, tail_pool = pools
        xc, z, g, beta = self._mix_in(x[:, 0])
        with jax.named_scope("gdn/conv"):
            tail = _from_lane_rows(tail_pool[layer, slots],
                                   self.cfg.linear_conv_kernel_dim,
                                   xc.shape[-1])
            conv, tail = conv_step(xc, tail, self.conv_weight)
            tail_pool = tail_pool.at[layer, slots].set(_to_lane_rows(tail))
            q, k, v = self._heads(conv)
        with jax.named_scope("gdn/step"):
            o, state_pool = gated_delta_decode(q, k, v, g, beta, state_pool,
                                               slots, layer=layer)
        return self._mix_out(x, o[:, None], z), (state_pool, tail_pool)

    def forward(self, x):
        return self.serve_prefill_state(x, x.shape[1])[0]


class OlmoHybridModel(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            weight_attr=_init(cfg))
        kinds = {"linear_attention": OlmoHybridLinearLayer,
                 "full_attention": OlmoHybridAttentionLayer}
        self.layers = nn.LayerList([kinds[t](cfg) for t in cfg.kinds])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = OlmoHybridModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids):
        """Full causal forward: ``[B, S] -> [B, S, V]`` (the logit at
        position ``i`` predicts token ``i + 1``)."""
        return self.logits(self.model(input_ids))

    # -- the serving engine's seam (serving/engine.py) ---------------------

    serve_counts = 0                    # nothing beside the token
    serve_latent_value_dim = None       # keys and values, not a latent row
    serve_generation = None             # a token a row a step

    def serve_cache_rows(self):
        """One fused row a token of a full layer: a page's keys and values
        are one stretch of one pool (``ops/paged_layout.py``)."""
        cfg = self.cfg
        return ((2 * cfg.num_key_value_heads, cfg.head_dim),)

    def serve_state(self):
        """What a linear layer keeps for a sequence, one pool each: the state
        ``[d_k, H * d_v]`` float32 (head ``h`` the columns ``h * d_v ..``:
        whole tiles on the chip) and the convolution's last ``K - 1`` inputs,
        flat in rows of 128 lanes (``[ceil((K - 1) * C / 128), 128]``, the
        last row zero-padded): whole tiles too, which the decode program
        writes by slot in place."""
        cfg = self.cfg
        nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        width = 2 * cfg.linear_num_key_heads * dk + nv * dv
        n_tail = (cfg.linear_conv_kernel_dim - 1) * width
        return (jax.ShapeDtypeStruct((dk, nv * dv), jnp.float32),
                jax.ShapeDtypeStruct((_tail_rows(n_tail), LANES),
                                     self.serve_dtype()))

    def serve_dtype(self):
        return self.model.embed_tokens.weight.dtype

    def serve_layers(self):
        return list(self.model.layers)

    def serve_embed(self, ids, pos):
        return self.model.embed_tokens(ids)

    def serve_final_norm(self, x):
        return self.model.norm(x)
