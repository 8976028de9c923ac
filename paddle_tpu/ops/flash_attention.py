"""Flash attention.

Reference: ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:324`` (FlashAttnKernel
dispatching to the vendored CUTLASS flash-attention; varlen variant at :289).

TPU-native: a Pallas kernel (``_pallas/flash_attention.py``) implementing the
standard online-softmax blocked algorithm tiled for the MXU (block sizes
multiples of 128), with a custom VJP whose backward is also a Pallas kernel.
Layout follows paddle's flash_attn: [batch, seq, heads, head_dim].
``FLAGS_use_pallas_kernels=0`` (or unsupported shapes/platform) falls back to
the jnp reference — numerically identical module-level semantics, used for
CPU tests and gradient checks.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import flags
from .paged_layout import gather_pages, heads_first, split_keys_values

__all__ = ["flash_attention", "flash_attn_unpadded", "reference_attention",
           "single_query_attention", "paged_single_query_attention",
           "takes_paged_kernel", "multi_query_attention",
           "block_paged_attention", "latent_attention",
           "latent_paged_attention"]


def _masked_softmax(scores, dtype):
    """Softmax over the last axis of float32 ``scores`` in which masked
    entries are ``-inf``; a row masked throughout gives zeros, not NaN."""
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(jnp.isfinite(scores),
                  jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    return (e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True),
                            1e-30)).astype(dtype)


def _causal_mask(sq: int, sk: int, causal_block: int = 1):
    """``[sq, sk]`` bool, bottom-right aligned: query ``i`` sees key ``j``
    iff ``j <= i + sk - sq``, or with ``causal_block = B > 1`` (block-causal,
    blocks counted from position 0) iff ``j // B <= (i + sk - sq) // B``."""
    qi = jnp.arange(sq)[:, None] + (sk - sq)
    if causal_block > 1:
        qi = qi // causal_block * causal_block + causal_block - 1
    return jnp.arange(sk)[None, :] <= qi


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        bias: Optional[jax.Array] = None,
                        causal_block: int = 1):
    """jnp reference, [B,S,H,D] layout, fp32 softmax. Handles grouped-query
    kv (fewer kv heads) and rows with no valid keys (output 0, matching the
    Pallas kernel). ``causal_block`` makes the causal mask block-causal (a
    query sees every key of its own block of that many positions)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        mask = _causal_mask(sq, sk, causal_block)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    # Masked-row-safe softmax: fully-masked rows (all -inf) produce 0, not
    # NaN — matching the Pallas kernels' handling.
    probs = _masked_softmax(scores, q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def single_query_attention(q, k, v, lengths=None,
                           scale: Optional[float] = None):
    """Decode-step attention: one query position over gathered KV.

    ``q`` is ``[B, 1, H, D]``; ``k``/``v`` are ``[B, Sk, KH, D]`` with
    ``KH`` dividing ``H`` — grouped-query KV is read through a head
    reshape (query head ``h`` uses kv head ``h // (H // KH)``, the same
    mapping as ``jnp.repeat`` on the head axis) so no repeated KV is ever
    materialized. ``lengths`` (``[B]`` int, optional) masks each row to
    its first ``lengths[b]`` keys — the serving engine's per-sequence
    context lengths over a padded gathered-KV batch; a row with zero
    valid keys returns 0 (the kernels' masked-row convention).

    With ``lengths=None`` this equals ``reference_attention(q, k, v,
    causal=True)`` at Sq=1 (the last causal row sees every key), without
    the dense path's ``[Sq, Sk]`` mask build, head-repeat, or recompute
    of the full score matrix machinery.
    """
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"single_query_attention needs Sq=1, got {sq}")
    sk, kh = k.shape[1], k.shape[2]
    if h % kh:
        raise ValueError(f"query heads ({h}) not a multiple of kv heads "
                         f"({kh})")
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q[:, 0].reshape(b, kh, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if lengths is not None:
        valid = jnp.arange(sk)[None, :] < jnp.asarray(lengths)[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    # Masked-row-safe softmax, matching reference_attention.
    probs = _masked_softmax(scores, q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v)
    return out.reshape(b, 1, h, d)


def _platform_of(x) -> str:
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    return jax.default_backend()


def takes_paged_kernel(q_dtype, k_pool, latent_value_dim=None,
                       block_size: Optional[int] = None) -> bool:
    """Does decode attention over ``k_pool`` with queries of ``q_dtype`` take
    its paged Pallas kernel? ``k_pool`` is one of the three pool shapes
    (``ops/paged_layout.py``): a K (or V) pool with its pages tokens first,
    ``[..., NB, bs, KH, D]`` (the single-query kernel); the same heads first,
    ``[..., NB, KH, bs, D]``, which is told apart by ``block_size`` (the
    block kernel, several queries a row, whose one pool holds fused rows of
    ``2 * KH`` heads); or, with ``latent_value_dim`` (the
    part of a row that is its value), a latent pool ``[..., NB, bs, W]``. As
    ``_use_pallas``: on a TPU with the flag on and a shape the kernel takes;
    an unsupported shape ON a TPU is announced once (P005). The serving
    engine asks too, to count what its decode program reads."""
    if not flags.flag("use_pallas_kernels") or _platform_of(k_pool) != "tpu":
        return False
    from ..analysis.pallas_check import report_fallback
    shape = (f"q {jnp.dtype(q_dtype).name} pool{tuple(k_pool.shape)} "
             f"{k_pool.dtype}")
    if latent_value_dim is not None:
        from ._pallas.latent_paged_attention import supported_shapes
        if supported_shapes(q_dtype, k_pool, latent_value_dim):
            return True
        report_fallback(
            "latent_paged_attention",
            f"{shape} value_dim {latent_value_dim}",
            "needs bf16 queries and pool, the row and value_dim multiples "
            "of 128 and block_size a multiple of 16")
        return False
    if block_size is not None and heads_first(k_pool, block_size):
        from ._pallas.block_paged_attention import supported_shapes
        if supported_shapes(q_dtype, k_pool):
            return True
        report_fallback(
            "block_paged_attention", shape,
            "needs bf16 queries and pool, an even number of heads a fused "
            "row, head_dim 128 and block_size a multiple of 16")
        return False
    from ._pallas.paged_attention import supported_shapes
    if supported_shapes(q_dtype, k_pool):
        return True
    report_fallback(
        "paged_single_query_attention", shape,
        "needs bf16 queries and pool, head_dim 128, block_size and kv "
        "heads multiples of 16")
    return False


def paged_single_query_attention(q, k_pool, v_pool, tables, lengths, *,
                                 block_size: int, layer=0,
                                 scale: Optional[float] = None):
    """Decode-step attention read through block tables: ``q [B, 1, H, D]``
    against the pages ``tables [B, M]`` names in the pool, row ``b`` up to
    its first ``lengths[b]`` keys (0: the row returns 0).

    The pool is the engine's ``[L, NB, block_size, KH, D]`` with ``layer``
    the layer to read (a Python int or a traced scalar), or one layer's
    ``[NB, block_size, KH, D]``. Handing over the whole pool and an index
    keeps a slice of it from ever being copied.

    On a TPU, for the shapes ``_pallas.paged_attention.supported_shapes``
    takes, this is the Pallas kernel: each row's pages are fetched from HBM
    up to its own length and no gathered copy exists. Everywhere else it is
    the dense path the kernel is checked against: gather every table's
    pages, then :func:`single_query_attention` behind a length mask."""
    if block_size not in k_pool.shape[-3:-1]:
        raise ValueError(f"pool pages {tuple(k_pool.shape[-3:])} hold no "
                         f"axis of block_size {block_size}")
    if not heads_first(k_pool, block_size) \
            and takes_paged_kernel(q.dtype, k_pool):
        from ._pallas.paged_attention import paged_attention_pallas
        from ..analysis import pallas_check as _pc
        _pc.enforce(_pc.spec_for_paged_decode(
            q.shape[0], tables.shape[1], block_size, q.shape[2],
            k_pool.shape[-2], q.shape[3], dtype=k_pool.dtype),
            where="paged_single_query_attention")
        return paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                      layer=layer, scale=scale)
    if k_pool.ndim == 5:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    keys = gather_pages(k_pool, tables, block_size)
    vals = gather_pages(v_pool, tables, block_size)
    return single_query_attention(q, keys, vals, lengths=lengths,
                                  scale=scale)


def block_paged_attention(q, kv_pool, tables, lengths, *,
                          block_size: int, layer=0,
                          scale: Optional[float] = None):
    """A block's attention read through block tables: ``q [B, Lq, H, D]``
    (the ``Lq`` positions of each row's block in flight) against the pages
    ``tables [B, M]`` names in the pool, every query of row ``b`` over the
    row's first ``lengths[b]`` keys (its context and the block itself, which
    the pass has written: within the block nothing is masked; 0: the row
    returns 0). Returns ``[B, Lq, H, D]``.

    The pool is the engine's ONE pool of fused rows (``ops/paged_layout.py``:
    a token's keys and values as one row of ``2 * KH`` heads, keys first),
    with ``layer`` the layer to read (a Python int or a traced scalar), or
    one layer's; its pages are heads first (``[.., NB, 2 * KH, block_size,
    D]``) or tokens first. On a TPU, for the heads-first shapes
    ``_pallas.block_paged_attention.supported_shapes`` takes, this is the
    Pallas kernel: each row's pages are fetched from HBM up to its own
    length, keys and values of a page as one descriptor, a kv head at a time
    against its ``Lq * H / KH`` query rows (every head in one product where
    that is one row), and no gathered copy exists.
    Everywhere else it is the dense path the kernel is checked against:
    gather every table's pages, split the rows into keys and values, then
    :func:`multi_query_attention` behind the length mask."""
    if takes_paged_kernel(q.dtype, kv_pool, None, block_size):
        from ._pallas.block_paged_attention import \
            block_paged_attention_pallas
        return block_paged_attention_pallas(
            q, kv_pool, tables, lengths, layer=layer, scale=scale)
    if kv_pool.ndim == 5:
        kv_pool = kv_pool[layer]
    keys, vals = split_keys_values(gather_pages(kv_pool, tables, block_size))
    pos = jnp.broadcast_to((jnp.asarray(lengths) - 1)[:, None], q.shape[:2])
    return multi_query_attention(q, keys, vals, pos, scale=scale)


def multi_query_attention(q, k, v, pos, scale: Optional[float] = None):
    """Offset-causal attention for the serving ``extend`` step: ``q`` is
    ``[B, L, H, D]`` (L query tokens at absolute positions ``pos``
    [B, L]); ``k``/``v`` are ``[B, Sk, KH, D]`` gathered pages. Query
    ``(b, i)`` attends keys ``j <= pos[b, i]`` (its own KV was
    scattered before the gather, so self-attention is included exactly
    like the decode step's ``lengths = pos + 1`` mask). Same GQA head
    reshape, f32 score accumulation, and masked-row-safe softmax as
    :func:`single_query_attention` (numeric agreement with the decode path
    is what keeps chunked / speculative outputs token-exact against
    ``model.generate``)."""
    b, L, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, L, kh, g, d)
    scores = jnp.einsum("blkgd,bskd->blkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(sk)[None, None, :] <= pos[:, :, None]   # [B, L, Sk]
    scores = jnp.where(valid[:, :, None, None, :], scores, -jnp.inf)
    probs = _masked_softmax(scores, q.dtype)
    out = jnp.einsum("blkgs,bskd->blkgd", probs, v)
    return out.reshape(b, L, h, d)


def latent_attention(q, rows, pos, *, value_dim: int, scale: float):
    """Absorbed latent (MLA) attention, dense: ``q [B, L, H, W]`` (each
    head's absorbed query ``[q_nope W_UK^T | q_rope]``) over the latent rows
    ``rows [B, Sk, W]`` (``[c_kv | k_rope]`` a token, the same for every
    head). Query ``(b, i)`` attends rows ``j <= pos[b, i]``; the value of a
    row is its first ``value_dim`` entries. Returns ``[B, L, H, value_dim]``.
    f32 scores and masked-row-safe softmax as
    :func:`single_query_attention`; this is the path off the chip and what
    the Pallas kernel is checked against."""
    scores = jnp.einsum("blhw,bsw->blhs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(rows.shape[1])[None, None, :] <= pos[:, :, None]
    scores = jnp.where(valid[:, :, None, :], scores, -jnp.inf)
    probs = _masked_softmax(scores, q.dtype)
    return jnp.einsum("blhs,bsv->blhv", probs, rows[..., :value_dim])


def latent_paged_attention(q, pool, tables, lengths, *, block_size: int,
                           value_dim: int, scale: float, layer=0):
    """Decode-step absorbed latent attention read through block tables:
    ``q [B, 1, H, W]`` against the latent rows that ``tables [B, M]`` names
    in ``pool`` (the engine's ``[L, NB, block_size, W]`` with ``layer`` the
    layer to read, or one layer's ``[NB, block_size, W]``), row ``b`` up to
    its first ``lengths[b]`` rows (0: the row returns 0). Returns
    ``[B, 1, H, value_dim]``.

    On a TPU, for the shapes
    ``_pallas.latent_paged_attention.supported_shapes`` takes, this is the
    Pallas kernel: each row's pages are fetched from HBM up to its own
    length and no gathered copy exists. Everywhere else it is the dense path
    the kernel is checked against: gather every table's pages, then
    :func:`latent_attention` behind a length mask."""
    if pool.shape[-2] != block_size:
        raise ValueError(f"pool pages hold {pool.shape[-2]} tokens, "
                         f"block_size says {block_size}")
    if takes_paged_kernel(q.dtype, pool, value_dim):
        from ._pallas.latent_paged_attention import \
            latent_paged_attention_pallas
        return latent_paged_attention_pallas(
            q, pool, tables, lengths, value_dim=value_dim, scale=scale,
            layer=layer)
    if pool.ndim == 4:
        pool = pool[layer]
    b = q.shape[0]
    rows = pool[tables].reshape(b, tables.shape[1] * block_size,
                                pool.shape[-1])
    return latent_attention(q, rows, (jnp.asarray(lengths) - 1)[:, None],
                            value_dim=value_dim, scale=scale)


def _use_pallas(q, k) -> bool:
    """Does this call take the Pallas kernel? Yes on a TPU with the flag
    on and a shape the kernels tile; off the chip the dense path is the
    declared one. An unsupported shape ON a TPU is announced once (P005),
    never swallowed."""
    if not flags.flag("use_pallas_kernels"):
        return False
    if _platform_of(q) != "tpu":
        return False
    # MXU-friendly shapes only (both seq lens tile-divisible); else the
    # reference path — the kernel would drop tail keys otherwise.
    from ._pallas.flash_attention import supported_shapes
    if supported_shapes(q, k):
        return True
    if q.shape[1] == 1:
        return False  # a decode step: single-query attention by design
    from ..analysis.pallas_check import report_fallback
    report_fallback(
        "flash_attention", f"q{tuple(q.shape)} k{tuple(k.shape)}",
        "needs both sequence lengths % 128 == 0 and head_dim in "
        "(64, 128, 256)")
    return False


def _dense_prob_dropout_attention(q, k, v, causal, scale, seed,
                                  rate: float):
    """Dense mirror of the kernel's attention-prob dropout: the SAME
    position-hashed mask (``dropout_keep_dense``), applied to the softmax
    probabilities (NOT the output — ref flash_attn_kernel.cu:44), so
    pallas and fallback paths agree bitwise under a fixed seed."""
    from ._pallas.flash_attention import dropout_keep_dense
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), sk - sq)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(jnp.isfinite(scores),
                  jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    keep = dropout_keep_dense(b * h, sq, sk, seed, rate)  # [BH, Sq, Sk]
    probs = (probs * keep.reshape(b, h, sq, sk)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(query, key, value=None, dropout: float = 0.0,
                    causal: bool = False, return_softmax: bool = False,
                    *, scale: Optional[float] = None, training: bool = True,
                    fixed_seed_offset=None, causal_block: int = 1):
    """paddle.nn.functional.flash_attention parity ([B,S,H,D]).

    ``dropout`` is attention-PROB dropout inside the kernel (ref
    flash_attn_kernel.cu:44): the mask is regenerated in backward from
    (position, seed) — the TPU-native form of the reference's saved-RNG-
    state recompute (:76). ``fixed_seed_offset`` pins the seed.

    ``causal_block = B > 1`` with ``causal`` is the block-causal mask of
    generation by diffusion over blocks: a query sees every key of its own
    block of ``B`` positions (counted from position 0) and all earlier ones,
    ``key <= query | (B - 1)`` for ``B`` a power of two. Forward only (no
    dropout, no gradient).

    ``value=None``: ``key`` is the fused cache row of a model that caches a
    token's keys and values as one (``[B, S, 2 * KH, D]``, keys the first
    ``KH`` heads: ``ops/paged_layout.split_keys_values``)."""
    if value is None:
        key, value = split_keys_values(key)
    if return_softmax:
        raise NotImplementedError("return_softmax is a debug-only GPU feature")
    if causal_block > 1:
        if not causal or (dropout > 0.0 and training):
            raise ValueError("causal_block needs causal=True and runs "
                             "forward only, without dropout")
        if causal_block & (causal_block - 1):
            raise ValueError(f"causal_block {causal_block} is not a power "
                             "of two")
        if _use_pallas(query, key):
            from ._pallas.flash_attention import flash_attention_pallas
            return flash_attention_pallas(query, key, value, causal=True,
                                          scale=scale,
                                          causal_block=causal_block)
        return reference_attention(query, key, value, True, scale,
                                   causal_block=causal_block)
    if dropout > 0.0 and training:
        if fixed_seed_offset is not None:
            seed = jnp.asarray(fixed_seed_offset, jnp.int32).reshape(1)
        else:
            from ..core.random import next_key
            seed = jax.random.randint(next_key(), (1,), 0, 2 ** 31 - 1,
                                      dtype=jnp.int32)
        if _use_pallas(query, key):
            from ._pallas.flash_attention import flash_attention_pallas
            return flash_attention_pallas(query, key, value, causal=causal,
                                          scale=scale, dropout=dropout,
                                          dropout_seed=seed)
        return _dense_prob_dropout_attention(query, key, value, causal,
                                             scale, seed, dropout)
    if _use_pallas(query, key):
        from ._pallas.flash_attention import flash_attention_pallas
        return flash_attention_pallas(query, key, value, causal=causal,
                                      scale=scale)
    if query.shape[1] == 1:
        # Decode step (Sq=1): the dense reference path would rebuild the
        # causal mask and the full repeated-KV score machinery for a
        # single row whose causal mask is all-visible — route through
        # the single-query helper instead.
        return single_query_attention(query, key, value, scale=scale)
    return reference_attention(query, key, value, causal, scale)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q: int, max_seqlen_k: int,
                        scale: Optional[float] = None, dropout: float = 0.0,
                        causal: bool = False):
    """Varlen parity (ref flash_attn_kernel.cu:289). XLA needs static
    shapes, so varlen is expressed with static totals (SURVEY §7
    hard-part (c)):

    - **fast path** (self-attention, tile-divisible packed length): run the
      Pallas kernel directly on the packed [1, total, H, D] layout with
      per-token segment ids — no padding FLOPs at all;
    - fallback: scatter to a padded batch + segment-mask dense reference.
    """
    b = cu_seqlens_q.shape[0] - 1
    total_q, h, d = query.shape
    # Causal masking in the packed kernel uses global positions, which
    # equals per-sequence causality only when q and k share boundaries;
    # cu values are traced (uninspectable), so require the same object.
    fast_ok = dropout == 0.0 and \
        (not causal or cu_seqlens_q is cu_seqlens_k)
    if fast_ok:
        q4 = query[None]
        k4 = key[None]
        v4 = value[None]
        if _use_pallas(q4, k4):
            from ._pallas.flash_attention import flash_attention_pallas

            def token_segments(cu, total, pad_sentinel):
                # token -> sequence index; tail padding (tokens past
                # cu[-1], if the caller padded the packed dim) gets a
                # side-specific sentinel so q-padding and k-padding never
                # match each other -> padded rows attend nothing and come
                # out as the kernel's masked-row zeros
                idx = jnp.arange(total)
                seg = jnp.searchsorted(cu, idx, side="right") - 1
                return jnp.where(idx < cu[-1], seg, pad_sentinel)

            # q and k carry their own boundaries: cross-attention packings
            # with different per-sequence splits stay correct
            seg_q = token_segments(cu_seqlens_q, total_q, -1)
            seg_k = token_segments(cu_seqlens_k, key.shape[0], -2)
            out = flash_attention_pallas(q4, k4, v4, causal=causal,
                                         scale=scale,
                                         segment_ids=seg_q[None],
                                         segment_ids_k=seg_k[None])
            return out[0]
    # Scatter the packed tokens into [B, max_seqlen, H, D].
    def to_padded(x, cu, max_len):
        out = jnp.zeros((b, max_len, x.shape[-2], x.shape[-1]), x.dtype)
        idx = jnp.arange(x.shape[0])
        seg = jnp.searchsorted(cu, idx, side="right") - 1
        pos = idx - cu[seg]
        return out.at[seg, pos].set(x)

    qp = to_padded(query, cu_seqlens_q, max_seqlen_q)
    kp = to_padded(key, cu_seqlens_k, max_seqlen_k)
    vp = to_padded(value, cu_seqlens_k, max_seqlen_k)
    lens_q = cu_seqlens_q[1:] - cu_seqlens_q[:-1]
    lens_k = cu_seqlens_k[1:] - cu_seqlens_k[:-1]
    qmask = jnp.arange(max_seqlen_q)[None, :] < lens_q[:, None]
    kmask = jnp.arange(max_seqlen_k)[None, :] < lens_k[:, None]
    bias = jnp.where(kmask[:, None, None, :], 0.0, -jnp.inf)
    out = reference_attention(qp, kp, vp, causal=causal, scale=scale, bias=bias)
    out = jnp.where(qmask[:, :, None, None], out, 0.0)
    # Pack back.
    idx = jnp.arange(total_q)
    seg = jnp.searchsorted(cu_seqlens_q, idx, side="right") - 1
    pos = idx - cu_seqlens_q[seg]
    return out[seg, pos]
