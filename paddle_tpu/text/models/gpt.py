"""GPT model family — the flagship (BASELINE config 4: GPT-3 1.3B hybrid
parallel).

A from-scratch decoder-only transformer built on the TP layer library: QKV
and MLP-up are column-parallel, attention-out and MLP-down are row-parallel
(Megatron sharding over the 'mp' mesh axis), attention runs through the
Pallas flash-attention op, and the lm head is the (optionally tied)
vocab-parallel projection with parallel cross-entropy. Compare the
reference's fleet GPT cases (test/collective/fleet hybrid_parallel_mp_model /
pp_model) which assemble the same structure from mp_layers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ... import nn
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import ParamAttr
from ...distributed.fleet.layers.mpu.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, _constrain, MP_AXIS)
from ...ops import flash_attention
from ...ops.paged_layout import gather_pages
from ...ops.flash_attention import (multi_query_attention,
                                    paged_single_query_attention)

__all__ = ["GPTConfig", "GPT", "GPTForCausalLM", "gpt3_1p3b", "gpt_tiny"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    # Grouped-query attention: fewer KV heads shared by query-head groups
    # (None = MHA). The Pallas flash kernel reads shared KV tiles through
    # its BlockSpec index map, so GQA adds no repeat materialization.
    num_kv_heads: Optional[int] = None
    max_position_embeddings: int = 2048
    intermediate_size: Optional[int] = None  # default 4*hidden
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True
    sequence_parallel: bool = False
    recompute: bool = False
    # jax.checkpoint_policies name used when recompute is on
    recompute_policy: str = "dots_and_flash_saveable"
    # Long-context CP over the 'sep' mesh axis: None | 'ring' | 'ulysses'.
    context_parallel: Optional[str] = None

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def kv_heads(self) -> int:
        # explicit None check: num_kv_heads=0 must be rejected by the
        # attention layer's validation, not silently become MHA
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)


def gpt3_1p3b(**overrides) -> "GPTConfig":
    """GPT-3 XL / 1.3B: 24 layers, d=2048, 16 heads."""
    return GPTConfig(**{**dict(hidden_size=2048, num_layers=24, num_heads=16),
                        **overrides})


def gpt_tiny(**overrides) -> "GPTConfig":
    return GPTConfig(**{**dict(vocab_size=1024, hidden_size=128, num_layers=2,
                               num_heads=4, max_position_embeddings=256),
                        **overrides})


def _cp_active() -> bool:
    from ...distributed.topology import get_hybrid_mesh
    mesh = get_hybrid_mesh()
    return mesh is not None and mesh.shape.get("sep", 1) > 1


def _init_attr(cfg: GPTConfig, spec=None) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range),
                     partition_spec=spec)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.kv_heads = cfg.kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        if self.kv_heads < 1 or self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.kv_heads})")
        # GSPMD shards the kv-head axis over mp: kv_heads % mp != 0 is
        # correct but silently uneven (idle shards + implicit resharding),
        # so surface it — a warning, since replicate-KV setups are legal.
        from ...distributed.topology import get_hybrid_mesh
        mesh = get_hybrid_mesh()
        if mesh is not None and "mp" in mesh.axis_names:
            mp = mesh.shape["mp"]
            if mp > 1 and self.kv_heads % mp:
                import warnings
                warnings.warn(
                    f"num_kv_heads ({self.kv_heads}) is not divisible by the "
                    f"mp mesh degree ({mp}): GSPMD shards the KV-head axis "
                    f"unevenly (idle shards + implicit resharding). Use a "
                    f"kv_heads multiple of mp, or lower mp.", UserWarning)
        h = cfg.hidden_size
        if self.kv_heads == self.num_heads:
            self.qkv_proj = ColumnParallelLinear(
                h, 3 * h, weight_attr=_init_attr(cfg), has_bias=True,
                gather_output=False)
        else:
            self.q_proj = ColumnParallelLinear(
                h, h, weight_attr=_init_attr(cfg), has_bias=True,
                gather_output=False)
            self.kv_proj = ColumnParallelLinear(
                h, 2 * self.kv_heads * self.head_dim,
                weight_attr=_init_attr(cfg), has_bias=True,
                gather_output=False)
        self.out_proj = RowParallelLinear(
            h, h, weight_attr=_init_attr(cfg), has_bias=True,
            input_is_parallel=True)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def _project_qkv(self, x):
        """-> q [b,s,H,D], k/v [b,s,KH,D], heads sharded over mp."""
        b, s, _ = x.shape
        # batch/seq dims stay UNCONSTRAINED: pinning them replicated forces
        # a replicate-then-repartition when the incoming activation is
        # dp/sep-sharded (SPMD involuntary-remat warning, dryrun[8])
        U = P.UNCONSTRAINED
        if self.kv_heads == self.num_heads:
            qkv = self.qkv_proj(x)  # [b, s, 3h] (h sharded over mp)
            qkv = qkv.reshape(b, s, 3, self.num_heads, self.head_dim)
            qkv = _constrain(qkv, P(U, U, U, MP_AXIS, U))
            return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = self.q_proj(x).reshape(b, s, self.num_heads, self.head_dim)
        q = _constrain(q, P(U, U, MP_AXIS, U))
        kv = self.kv_proj(x).reshape(b, s, 2, self.kv_heads, self.head_dim)
        kv = _constrain(kv, P(U, U, U, MP_AXIS, U))
        return q, kv[:, :, 0], kv[:, :, 1]

    def _repeat_kv(self, k, v):
        rep = self.num_heads // self.kv_heads
        if rep == 1:
            return k, v
        return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)

    def forward(self, x):
        b, s, h = x.shape
        q, k, v = self._project_qkv(x)
        if self.cfg.context_parallel and _cp_active():
            from ...distributed.context_parallel import (ring_attention,
                                                         ulysses_attention)
            if self.cfg.context_parallel not in ("ring", "ulysses"):
                raise ValueError(
                    f"context_parallel={self.cfg.context_parallel!r}; "
                    "expected 'ring' or 'ulysses'")
            if self.cfg.attention_dropout > 0.0 and self.training:
                raise NotImplementedError(
                    "attention_dropout > 0 is not supported with context "
                    "parallelism (probs are never materialized globally)")
            if self.cfg.context_parallel == "ring":
                # ring's block attention contracts equal head counts;
                # broadcast grouped KV for it only.
                out = ring_attention(q, *self._repeat_kv(k, v), causal=True)
            else:
                # ulysses repeats KV just enough for the head all-to-all —
                # pass the grouped tensors through untouched.
                out = ulysses_attention(q, k, v, causal=True)
        elif self.cfg.use_flash_attention:
            # flash handles grouped KV natively (index-mapped tiles)
            out = flash_attention(q, k, v, dropout=self.cfg.attention_dropout,
                                  causal=True, training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, *self._repeat_kv(k, v), is_causal=True,
                dropout_p=self.cfg.attention_dropout,
                training=self.training)
        out = out.reshape(b, s, h)
        out = self.out_proj(out)
        return self.dropout(out)

    def decode(self, x, cache, offset):
        """Incremental attention with a KV cache.

        x: [b, s, h] new tokens (s = prompt len at prefill, 1 per decode
        step); cache: (k, v) each [b, max_len, heads, head_dim]; offset:
        traced scalar — how many positions are already cached. Returns
        (out [b, s, h], new_cache). The cache is written with
        dynamic_update_slice (traced offsets compose with lax.scan), and
        attention masks keys past offset+s plus intra-block causality.
        """
        b, s, h = x.shape
        q, k, v = self._project_qkv(x)
        k_cache, v_cache = cache                     # [b, max, KH, D]
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, offset, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, offset, 0, 0))
        max_len = k_cache.shape[1]
        q_pos = offset + jnp.arange(s)              # [s]
        k_pos = jnp.arange(max_len)                 # [max_len]
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]  # [1,1,s,max]
        out = F.scaled_dot_product_attention(
            q, *self._repeat_kv(k_cache, v_cache), attn_mask=mask,
            is_causal=False, training=False)
        out = self.out_proj(out.reshape(b, s, h))
        return out, (k_cache, v_cache)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.up = ColumnParallelLinear(cfg.hidden_size, cfg.ffn_size,
                                       weight_attr=_init_attr(cfg),
                                       gather_output=False)
        self.down = RowParallelLinear(cfg.ffn_size, cfg.hidden_size,
                                      weight_attr=_init_attr(cfg),
                                      input_is_parallel=True)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def forward(self, x):
        x = self.up(x)
        x = F.gelu(x, approximate=True)
        x = self.down(x)
        return self.dropout(x)


class GPTBlock(nn.Layer):
    """Pre-LN decoder block."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)

    def _inner(self, x):
        if self.cfg.sequence_parallel:
            from ...distributed.fleet.utils.sequence_parallel_utils import \
                sequence_parallel_constraint
            x = sequence_parallel_constraint(x)
        if self.cfg.context_parallel and _cp_active():
            # Keep activations sequence-sharded over sep between blocks.
            x = _constrain(x, P(None, "sep", None))
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return x

    def forward(self, x):
        if self.cfg.recompute and self.training:
            # Policy swept on the 1.3B shape (r3/r4): full recompute
            # (dots_with_no_batch_dims_saveable) costs ~25% step time;
            # saving fwd matmul outputs (dots_saveable) trades ~290 MB/
            # layer of bf16 activations for most of that time back — and
            # additionally saving the flash kernel's (o, lse) residuals
            # plus LayerNorm outputs (dots_and_flash_saveable) skips the
            # in-backward flash re-run (~1 ms/layer) and LN recomputes
            # (~1.6 ms each) for ≈ +98 MB/layer. The BASELINE layout
            # (mp=4) quarters the per-chip share.
            from ...distributed.fleet.utils.recompute import RecomputePolicy
            policy = RecomputePolicy.resolve(self.cfg.recompute_policy)
            return jax.checkpoint(self._inner, policy=policy)(x)
        return self._inner(x)

    def decode(self, x, cache, offset):
        attn_out, cache = self.attn.decode(self.ln_1(x), cache, offset)
        x = x + attn_out
        x = x + self.mlp(self.ln_2(x))
        return x, cache

    # -- the serving engine's layer step (serving/engine.py) ---------------

    def serve_project(self, x, pos):
        """-> (q, the token's page rows (k, v)); positions were added at
        the embedding."""
        q, k, v = self.attn._project_qkv(self.ln_1(x))
        return q, (k, v)

    def serve_attend_prefill(self, q, rows):
        return flash_attention(q, *rows, causal=True, training=False)

    def serve_attend_paged(self, q, pools, tables, lengths, block_size,
                           layer):
        return paged_single_query_attention(
            q, *pools, tables, lengths, block_size=block_size, layer=layer)

    def serve_attend_extend(self, q, pools, tables, pos, block_size, layer):
        keys, vals = (gather_pages(p[layer], tables, block_size)
                      for p in pools)
        return multi_query_attention(q, keys, vals, pos)

    def serve_finish(self, x, o, real):
        """The rest of the block after attention; no counts."""
        x = x + self.attn.out_proj(o.reshape(x.shape[0], x.shape[1], -1))
        return x + self.mlp(self.ln_2(x)), None


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=_init_attr(cfg, P(MP_AXIS, None)))
        self.wpe = nn.Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
            weight_attr=_init_attr(cfg))
        self.drop = nn.Dropout(cfg.hidden_dropout)
        self.h = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = jnp.arange(s)[None, :]
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        for block in self.h:
            x = block(x)
        return self.ln_f(x)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        head_dim = self.cfg.hidden_size // self.cfg.num_heads
        shape = (batch, max_len, self.cfg.kv_heads, head_dim)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in self.h]

    def decode(self, input_ids, caches, offset):
        """Forward with KV caches. input_ids [b, s]; offset = number of
        already-cached positions (traced). Returns (hidden, new_caches)."""
        b, s = input_ids.shape
        pos = offset + jnp.arange(s)[None, :]
        x = self.wte(input_ids) + self.wpe(pos)
        new_caches = []
        for block, cache in zip(self.h, caches):
            x, cache = block.decode(x, cache, offset)
            new_caches.append(cache)
        return self.ln_f(x), new_caches


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPT(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, weight_attr=_init_attr(cfg),
                has_bias=False, gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def logits(self, hidden):
        if self.cfg.tie_word_embeddings:
            w = self.gpt.wte.weight  # [vocab(mp-sharded), hidden]
            logits = jnp.matmul(hidden, w.T)
            return _constrain(logits, P(None, None, MP_AXIS))
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None):
        hidden = self.gpt(input_ids)
        logits = self.logits(hidden)
        if labels is None:
            return logits
        loss = self.loss_fn(logits, labels)
        return jnp.mean(loss)

    # -- the serving engine's seam (serving/engine.py) ---------------------

    serve_counts = 0        # the programs return nothing beside the token
    serve_latent_value_dim = None       # keys and values, not a latent row
    serve_generation = None             # a token a row a step

    def serve_cache_rows(self):
        """A token's page rows: keys and values a kv head."""
        row = (self.cfg.kv_heads, self.cfg.hidden_size // self.cfg.num_heads)
        return (row, row)

    def serve_dtype(self):
        return self.gpt.wte.weight.dtype

    def serve_layers(self):
        return list(self.gpt.h)

    def serve_embed(self, ids, pos):
        return self.gpt.wte(ids) + self.gpt.wpe(pos)

    def serve_final_norm(self, x):
        return self.gpt.ln_f(x)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0):
        """Autoregressive decoding with a KV cache
        (ref paddlenlp-style generate; decode loop is one lax.scan —
        compiled once, MXU matmuls per step).

        Returns [b, prompt_len + max_new_tokens] token ids; positions after
        an emitted eos are padded with eos.
        """
        input_ids = jnp.asarray(input_ids)
        b, prompt_len = input_ids.shape
        total = prompt_len + max_new_tokens
        if total > self.cfg.max_position_embeddings:
            raise ValueError(
                f"prompt {prompt_len} + max_new_tokens {max_new_tokens} "
                f"exceeds max_position_embeddings "
                f"{self.cfg.max_position_embeddings}")
        if max_new_tokens <= 0:
            return input_ids
        was_training = self.training
        self.eval()  # dropout must be off in the decode loop
        # Cache dtype must match the activations (bf16 under AMP O2).
        act_dtype = self.gpt.wte.weight.dtype
        caches = self.gpt.init_cache(b, total, dtype=act_dtype)
        hidden, caches = self.gpt.decode(input_ids, caches, 0)
        key = jax.random.PRNGKey(seed)

        def pick(logits, key):
            logits = logits / jnp.maximum(temperature, 1e-6)
            if not do_sample:
                return jnp.argmax(logits, axis=-1)
            if top_k:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # smallest set with cumulative prob >= top_p (keep the
                # first token crossing the threshold)
                cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
                cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx,
                                             axis=-1)
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1)

        key, sub = jax.random.split(key)
        next_tok = pick(self.logits(hidden[:, -1:])[:, 0], sub)
        finished = (next_tok == eos_token_id) \
            if eos_token_id is not None else None

        def step(carry, _):
            caches, tok, offset, key, finished = carry
            hidden, caches = self.gpt.decode(tok[:, None], caches, offset)
            key, sub = jax.random.split(key)
            nxt = pick(self.logits(hidden)[:, 0], sub)
            if finished is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            return (caches, nxt, offset + 1, key, finished), nxt

        if max_new_tokens > 1:
            (_, _, _, _, _), rest = jax.lax.scan(
                step, (caches, next_tok, jnp.asarray(prompt_len), key,
                       finished),
                None, length=max_new_tokens - 1)
            rest = jnp.swapaxes(rest, 0, 1)  # [b, T-1]
            out = jnp.concatenate([input_ids, next_tok[:, None], rest],
                                  axis=1)
        else:
            out = jnp.concatenate([input_ids, next_tok[:, None]], axis=1)
        if was_training:
            self.train()
        return out
