"""Flash attention as Pallas TPU kernels (fwd + bwd).

Online-softmax blocked attention (Dao et al.) tiled for the MXU. The key/
value sequence is STREAMED through VMEM via a third grid axis (TPU grids
iterate sequentially per core, so the online-softmax state lives in VMEM
scratch across the inner key-block steps) — VMEM usage is O(block) however
long the sequence, which is the point of flash attention. Backward
recomputes probabilities from the saved logsumexp (no O(S^2) residuals),
split into a dq kernel (inner loop over key blocks) and a dk/dv kernel
(inner loop over query blocks) so each output is accumulated by exactly one
program — no atomics.

Reference parity: ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:324``
(FlashAttnKernel → vendored CUTLASS flash-attn). Layout in/out is paddle's
[batch, seq, heads, head_dim]; internally [batch*heads, seq, head_dim].
Grouped-query attention keeps KV at [batch*kv_heads, seq, head_dim]: the
BlockSpec index maps route each query head to its shared KV tile, so GQA
never materializes repeated K/V (dK/dV fold the query-head groups after the
kernel). Causal masking is bottom-right aligned (query i attends keys <=
i + sk - sq), matching flash-attn decode semantics for sq != sk.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "supported_shapes"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30

from ...core import flags as _flags  # noqa: E402

# Sweep hooks: set both to a 128-multiple (paddle.set_flags or
# FLAGS_flash_block_q/_k env vars) to override the tuned table; 0 = auto.
for _n in ("flash_block_q", "flash_block_k"):
    if _n not in _flags.get_flags():
        _flags.define_flag(_n, 0, "flash-attention block override (0=auto)")
if "flash_head_pack" not in _flags.get_flags():
    _flags.define_flag(
        "flash_head_pack", 1,
        "route d=64 dense-head attention to the head-packed kernel")


def _tuned_blocks(sq: int, sk: int, d: int):
    """Cached autotune result for this shape class, or None."""
    from .autotune import get_cache
    hit = get_cache().get("flash_attention", f"sq{sq}_sk{sk}_d{d}")
    return tuple(hit) if hit else None


def tune_flash_blocks(query, key, value, causal: bool = False,
                      candidates=None, iters: int = 3):
    """On-device sweep of (block_q, block_k) for this shape; persists the
    winner so _pick_blocks uses it from then on (incl. at trace time).
    Call eagerly (not under jit) with representative inputs."""
    from .autotune import autotune
    b, sq, h, d = query.shape
    sk = key.shape[1]
    cands = candidates or [(256, 256), (512, 512), (512, 1024),
                           (1024, 512), (1024, 1024), (2048, 1024)]
    cands = [(bq, bk) for bq, bk in cands
             if sq % min(bq, sq) == 0 and sk % min(bk, sk) == 0]

    def run(cfg):
        bq, bk = cfg
        return flash_attention_pallas(query, key, value, causal=causal,
                                      block_q=bq, block_k=bk)

    return autotune("flash_attention", f"sq{sq}_sk{sk}_d{d}", cands, run,
                    iters=iters)


def _pick_blocks(sq: int, sk: int, d: int) -> tuple:
    """Autotuned (block_q, block_k) per head_dim for v5e-class VMEM: larger
    blocks amortize the sequential-grid overhead and keep the MXU busy
    (measured 1.8x over 128/128 at seq 1024, d 64). Returns the largest
    128-multiple <= the tuned target that divides the sequence length.
    ``flash_block_q``/``flash_block_k`` flags override (sweep hook)."""
    ov_q = int(_flags.flag("flash_block_q"))
    ov_k = int(_flags.flag("flash_block_k"))
    if ov_q or ov_k:
        if not (ov_q and ov_k):
            raise ValueError(
                f"flash_block_q/flash_block_k must be set together "
                f"(got q={ov_q}, k={ov_k}); set both or neither")
        if ov_q % 128 or ov_k % 128:
            raise ValueError(
                f"flash block overrides must be multiples of 128; got "
                f"q={ov_q}, k={ov_k}")
        tq, tk = ov_q, ov_k
    elif (tuned := _tuned_blocks(sq, sk, d)) is not None:
        # persistent autotune cache beats the static table (ref
        # phi/kernels/autotune/cache.h); populate via tune_flash_blocks()
        tq, tk = tuned
    elif d <= 64:
        tq, tk = 512, 1024
    elif d <= 128:
        # swept on the 254M GPT bench step (B16 S1024 H8): 1024/1024 =
        # 221.6ms vs 512/512 = 229.4ms (fewer grid steps, bigger MXU tiles)
        tq, tk = 1024, 1024
    elif d <= 256:
        # swept on the latent prefill's padded head (192 -> 256, 128 heads,
        # causal forward; my chip run, PR 30): at S 4096 512/512 = 13.1 ms,
        # 1024/1024 = 13.2, 256/512 = 21.6, 128/256 = 44.4; at S 1024
        # 1.94 / 1.85 / 2.48 / 4.02
        tq, tk = 512, 512
    else:
        tq, tk = 128, 256

    def fit(target, s):
        b = min(target, s)
        while b > 128 and s % b:
            b -= 128
        return b

    return fit(tq, sq), fit(tk, sk)


def _mix32(x):
    """murmur3 finalizer — a stateless uint32 mixer. Used for the dropout
    mask so forward and both backward kernels regenerate the IDENTICAL
    mask from (position, seed) with plain vector ops (the reference saves
    CUDA RNG state for the same purpose, flash_attn_kernel.cu:76; the
    pltpu hardware PRNG has no interpret-mode lowering, a jnp mixer runs
    everywhere and is exactly mirrorable in the dense reference)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def _keep_threshold(rate: float) -> int:
    """uint32 threshold: hash < threshold -> DROP (P = rate)."""
    return min(int(rate * 4294967296.0), 4294967295)


def _dropout_keepf(shape, bh, qi, kj, block_q, block_k, seq_q, seq_k,
                   seed, rate: float):
    """[shape] f32 factor: 0 where dropped, 1/keep_prob where kept."""
    q_pos = (jnp.uint32(qi) * jnp.uint32(block_q)
             + jax.lax.broadcasted_iota(jnp.uint32, shape, 0))
    k_pos = (jnp.uint32(kj) * jnp.uint32(block_k)
             + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    idx = (jnp.uint32(bh) * jnp.uint32(seq_q) + q_pos) \
        * jnp.uint32(seq_k) + k_pos
    h = _mix32(idx ^ seed.astype(jnp.uint32))
    keep = h >= jnp.uint32(_keep_threshold(rate))
    return keep.astype(jnp.float32) * (1.0 / (1.0 - rate))


def dropout_keep_dense(bh, sq, sk, seed, rate: float):
    """Dense mirror of the in-kernel mask: [BH, Sq, Sk] f32 keep factors.
    The CPU/reference path uses this so flash-with-dropout is bitwise
    consistent across backends under a fixed seed."""
    q_pos = jax.lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 1)
    k_pos = jax.lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 2)
    b_idx = jax.lax.broadcasted_iota(jnp.uint32, (bh, sq, sk), 0)
    idx = (b_idx * jnp.uint32(sq) + q_pos) * jnp.uint32(sk) + k_pos
    h = _mix32(idx ^ jnp.asarray(seed).astype(jnp.uint32))
    keep = h >= jnp.uint32(_keep_threshold(rate))
    return keep.astype(jnp.float32) * (1.0 / (1.0 - rate))


def _causal_mask(s, qi, kj, block_q, block_k, offset, causal_block=1):
    """Bottom-right-aligned causal mask (query i attends keys <= i + offset,
    offset = sk - sq). With ``causal_block = B`` (a power of two that divides
    the tiles) the mask is block-causal: keys ``<= (i + offset) | (B - 1)``,
    the query's own block of ``B`` positions whole. A tile's last query ends
    a block, so which tiles lie in the band does not change: only the tiles
    on the diagonal differ from the causal ones."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, dimension=0) + offset
    if causal_block > 1:
        q_pos = q_pos | (causal_block - 1)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, dimension=1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward: grid (bh, num_q_blocks, num_k_blocks), k innermost (streamed).
# ---------------------------------------------------------------------------

def _seg_mask(s, segq_ref, segk_ref):
    """Cross-segment entries get NEG_INF (packed-varlen attention).
    seg refs hold one int32 per position, [1, block] rows."""
    seg_q = segq_ref[0].T        # [bq, 1]
    seg_k = segk_ref[0]          # [1, bk]
    return jnp.where(seg_q == seg_k, s, NEG_INF)


def _ind01(cond):
    """bool -> {0,1} int32 for arithmetic-only index maps (works on both
    traced scalars and Python bools)."""
    return cond.astype(jnp.int32) if hasattr(cond, "astype") \
        else jnp.int32(cond)


def _can_pair(causal, sq, sk, nq, nk):
    """Shared fwd/bwd gate for the triangular enumeration — the two
    directions must pair under exactly the same condition."""
    return causal and sq == sk and nq == nk and nq % 2 == 0 and nq >= 2


def _paired_qi_kj(p, t, nq):
    """FlashAttention-2-style triangular enumeration for causal sq == sk:
    pair row p (p+1 in-band key blocks) with row nq-1-p (nq-p blocks) —
    every pair runs exactly nq+1 steps, and NO fully-masked block is ever
    fetched. Step t <= p works on (row p, key t); later steps on
    (row nq-1-p, key t-p-1). Arithmetic-only so it can serve as a
    BlockSpec index map."""
    c = _ind01(t <= p)
    qi = c * p + (1 - c) * (nq - 1 - p)
    kj = c * t + (1 - c) * (t - p - 1)
    return qi, kj


def _fwd_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, seed_ref,
                bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, segmented, block_q, block_k, seq_q, seq_k,
                dropout=0.0, biased=False, paired_nq=None, causal_block=1):
    bh_id = pl.program_id(0)  # hoisted: program_id inside pl.when bodies
    # has no interpret-mode lowering
    if paired_nq is None:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        nk = pl.num_programs(2)
        first = kj == 0
        last = kj == nk - 1
    else:
        p = pl.program_id(1)
        t = pl.program_id(2)
        qi, kj = _paired_qi_kj(p, t, paired_nq)
        first = jnp.logical_or(t == 0, t == p + 1)
        last = jnp.logical_or(t == p, t == paired_nq)
    offset = seq_k - seq_q

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: key blocks fully above the diagonal contribute nothing (the
    # paired enumeration never visits them at all).
    in_band = jnp.asarray(True) if not causal or paired_nq is not None \
        else kj * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(in_band)
    def _step():
        # Dots run on the MXU in the input dtype (bf16-native) with fp32
        # accumulation via preferred_element_type — casting up to fp32 first
        # would quarter MXU throughput.
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = _dot(q, kb, ((1,), (1,))) * scale  # [bq, bk] fp32
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset,
                             causal_block)
        if segmented:
            s = _seg_mask(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]  # [1, bk] additive key bias, broadcast
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Zero out fully-masked entries: rows with no valid keys have
        # s == m_new == NEG_INF and exp(0) would silently average V.
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        # attention-prob dropout: the softmax DENOMINATOR uses the
        # undropped p; only the PV accumulation is masked+rescaled
        # (ref flash_attn_kernel.cu:44 — dropout on P, not on the output)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = p
        if dropout > 0.0:
            pv = p * _dropout_keepf(p.shape, bh_id, qi, kj,
                                    block_q, block_k, seq_q, seq_k,
                                    seed_ref[0], dropout)
        acc_scr[...] = acc_scr[...] * alpha + _dot(pv.astype(vb.dtype), vb,
                                                   ((1,), (0,)))

    @pl.when(last)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        # lse is stored [BH, 1, Sq] (a single sublane row per program) —
        # broadcasting it across a 128-lane minor dim would cost 128x the
        # HBM for a per-row scalar.
        lse_ref[0] = (m_scr[...][:, :1] + jnp.log(l[:, :1])).T


def _segments_or_dummy(seg_q, seg_k, bh, sq, sk):
    """Kernels take segment refs unconditionally (one code path); the dense
    case feeds a [BH, 1, 1]-broadcastable dummy the specs tile for free."""
    segmented = seg_q is not None
    if not segmented:
        seg_q = jnp.zeros((bh, 1, sq), jnp.int32)
        seg_k = jnp.zeros((bh, 1, sk), jnp.int32)
    return segmented, seg_q, seg_k


def _kv_index(h: int, hk: int):
    """Grid row (= b*h + head) -> row of the [B*HK, S, D] KV array: query
    head g maps to KV head (g % h) // (h // hk) — grouped-query KV tiles
    are read through the index map, never materialized per query head."""
    rep = h // hk

    def index(b, i, j):
        return ((b // h) * hk + (b % h) // rep, j, 0)

    return index


def _bias_or_dummy(bias, b, sk):
    """bias: [B, 1, Sk] f32 additive key bias, or None -> dummy zeros."""
    biased = bias is not None
    if not biased:
        bias = jnp.zeros((b, 1, sk), jnp.float32)
    return biased, bias


def _fwd(q, k, v, scale, causal, block_q, block_k, num_heads,
         seg_q=None, seg_k=None, dropout=0.0, seed=None, bias=None,
         causal_block=1):
    """q: [BH, S, D]; k: [B*HK, S, D]; v: [B*HK, S, DV] (+ optional
    [BH, 1, S] int32 segment ids) -> (o [BH, Sq, DV], lse [BH, 1, Sq]
    fp32). ``DV`` is ``D`` wherever a backward follows; the forward alone
    takes values of another head size (latent attention: 192-wide keys
    padded to 256 beside 128-wide values)."""
    bh, sq, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[1]
    h = num_heads
    hk = k.shape[0] // (bh // h)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    segmented, seg_q, seg_k = _segments_or_dummy(seg_q, seg_k, bh, sq, sk)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    biased, bias = _bias_or_dummy(bias, bh // h, sk)
    nq, nk = sq // block_q, sk // block_k
    # Triangular enumeration for causal equal-length attention: pair rows
    # so no fully-masked key block is ever DMA'd (grid nq*nk ->
    # (nq/2)*(nq+1), a ~2x program cut at large nq, 25% at nq=2).
    paired = _can_pair(causal, sq, sk, nq, nk)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             segmented=segmented, block_q=block_q,
                             block_k=block_k, seq_q=sq, seq_k=sk,
                             dropout=dropout, biased=biased,
                             paired_nq=nq if paired else None,
                             causal_block=causal_block)
    kv_index = _kv_index(h, hk)
    if paired:
        grid = (bh, nq // 2, nq + 1)

        def qi_of(b, p, t):
            return _paired_qi_kj(p, t, nq)[0]

        def kj_of(b, p, t):
            return _paired_qi_kj(p, t, nq)[1]

        in_specs = [
            pl.BlockSpec((1, block_q, d),
                         lambda b, p, t: (b, qi_of(b, p, t), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, p, t: kv_index(b, qi_of(b, p, t),
                                                  kj_of(b, p, t))),
            pl.BlockSpec((1, block_k, dv),
                         lambda b, p, t: kv_index(b, qi_of(b, p, t),
                                                  kj_of(b, p, t))),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, p, t: (b, 0, qi_of(b, p, t))),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, p, t: (b, 0, kj_of(b, p, t))),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, p, t, _h=num_heads:
                         (b // _h, 0, kj_of(b, p, t))),
        ]
        out_specs = [
            pl.BlockSpec((1, block_q, dv),
                         lambda b, p, t: (b, qi_of(b, p, t), 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, p, t: (b, 0, qi_of(b, p, t))),
        ]
    else:
        grid = (bh, nq, nk)
        in_specs = [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j, _h=num_heads: (b // _h, 0, j)),
        ]
        out_specs = [
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ]
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * sq * sk * (d + dv) // (2 if causal else 1),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * sq * sk,
        ),
    )(q, k, v, seg_q, seg_k, seed, bias)
    return o, lse


# ---------------------------------------------------------------------------
# Backward dq: grid (bh, num_q_blocks, num_k_blocks), k streamed.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   segq_ref, segk_ref, seed_ref, bias_ref, dq_ref, dq_scr,
                   *, scale, causal, segmented, block_q, block_k,
                   seq_q, seq_k, dropout=0.0, biased=False, paired_nq=None):
    bh_id = pl.program_id(0)
    if paired_nq is None:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        nk = pl.num_programs(2)
        first = kj == 0
        last = kj == nk - 1
    else:
        p = pl.program_id(1)
        t = pl.program_id(2)
        qi, kj = _paired_qi_kj(p, t, paired_nq)
        first = jnp.logical_or(t == 0, t == p + 1)
        last = jnp.logical_or(t == p, t == paired_nq)
    offset = seq_k - seq_q

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    in_band = jnp.asarray(True) if not causal or paired_nq is not None \
        else kj * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(in_band)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0].T    # [1, bq] row -> [bq, 1] column
        delta = delta_ref[0].T
        kb = k_ref[0]
        vb = v_ref[0]
        s = _dot(q, kb, ((1,), (1,))) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        if segmented:
            s = _seg_mask(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]
        p = jnp.exp(s - lse) * (s > NEG_INF / 2)
        dp = _dot(do, vb, ((1,), (1,)))
        if dropout > 0.0:
            # dP = dPdropped * keepf; delta = rowsum(dO*O) already equals
            # rowsum(P*dP) under dropout (O was built from the masked P)
            dp = dp * _dropout_keepf(p.shape, bh_id, qi, kj,
                                     block_q, block_k, seq_q, seq_k,
                                     seed_ref[0], dropout)
        ds = (p * (dp - delta) * scale).astype(kb.dtype)
        dq_scr[...] = dq_scr[...] + _dot(ds, kb, ((1,), (0,)))

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward dk/dv: grid (bh, num_k_blocks, num_q_blocks), q streamed.
# ---------------------------------------------------------------------------

def _paired_kj_qi(p, t, nq):
    """Column pairing for the dkv kernel (causal, sq == sk): column p
    (nq-p in-band query blocks) pairs with column nq-1-p (p+1 blocks) —
    nq+1 steps per pair, no masked block fetched."""
    ci = _ind01(t < nq - p)
    kj = ci * p + (1 - ci) * (nq - 1 - p)
    qi = ci * (p + t) + (1 - ci) * (t - 1)
    return kj, qi


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    segq_ref, segk_ref, seed_ref, bias_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, scale, causal, segmented, block_q, block_k,
                    seq_q, seq_k, num_q_blocks=None, paired_nq=None,
                    dropout=0.0, biased=False, gqa_dims=None):
    if paired_nq is not None:
        p = pl.program_id(1)
        t = pl.program_id(2)
        kj, qi = _paired_kj_qi(p, t, paired_nq)
        first = jnp.logical_or(t == 0, t == paired_nq - p)
        last = jnp.logical_or(t == paired_nq - p - 1, t == paired_nq)
    else:
        kj = pl.program_id(1)
        t = pl.program_id(2)
        nt = pl.num_programs(2)
        # Grouped-query: the last grid axis runs rep * num_q_blocks steps —
        # every query head sharing this KV head streams through, and dk/dv
        # accumulate across the whole group IN the scratch (no per-query-
        # head dk/dv materialization, no post-kernel fold).
        qi = t if num_q_blocks is None else t % num_q_blocks
        first = t == 0
        last = t == nt - 1
    offset = seq_k - seq_q

    bkv_id = pl.program_id(0)

    def query_bh():
        """Flat QUERY-head row for the dropout hash — must match the bh
        the fwd/dq kernels used for this (q, k) tile."""
        if gqa_dims is None:
            return bkv_id
        h, hk, rep = gqa_dims
        if rep == 1:
            return bkv_id
        return (bkv_id // hk) * h + (bkv_id % hk) * rep \
            + t // num_q_blocks

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    in_band = jnp.asarray(True) if not causal or paired_nq is not None \
        else (qi + 1) * block_q - 1 + offset >= kj * block_k

    @pl.when(in_band)
    def _step():
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        lse = lse_ref[0].T    # [1, bq] row -> [bq, 1] column
        delta = delta_ref[0].T
        s = _dot(qb, kb, ((1,), (1,))) * scale  # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        if segmented:
            s = _seg_mask(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]
        p = jnp.exp(s - lse) * (s > NEG_INF / 2)
        pv = p
        dp = _dot(dob, vb, ((1,), (1,)))
        if dropout > 0.0:
            keepf = _dropout_keepf(p.shape, query_bh(), qi, kj, block_q,
                                   block_k, seq_q, seq_k, seed_ref[0],
                                   dropout)
            pv = p * keepf   # dV uses the MASKED probabilities
            dp = dp * keepf  # dP = dPdropped * keepf
        dv_scr[...] = dv_scr[...] + _dot(pv.astype(dob.dtype), dob,
                                         ((0,), (0,)))
        ds = (p * (dp - delta) * scale).astype(qb.dtype)
        dk_scr[...] = dk_scr[...] + _dot(ds, qb, ((0,), (0,)))

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k, num_heads,
         seg_q=None, seg_k=None, dlse=None, dropout=0.0, seed=None,
         bias=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    h = num_heads
    b_ = bh // h
    hk = k.shape[0] // b_
    rep = h // hk
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    segmented, seg_q, seg_k = _segments_or_dummy(seg_q, seg_k, bh, sq, sk)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    biased, bias = _bias_or_dummy(bias, b_, sk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [BH, Sq]
    delta = delta[:, None, :]  # [BH, 1, Sq] — matches the slim lse layout
    if dlse is not None:
        # lse cotangent (ring-attention merge differentiates through lse):
        # dL/ds_ij = p_ij (dp_ij - delta_i + dlse_i), so fold -dlse into the
        # delta the kernels already subtract.
        delta = delta - dlse.astype(jnp.float32)
    kv_index = _kv_index(h, hk)

    nqb, nkb = sq // block_q, sk // block_k
    dq_paired = _can_pair(causal, sq, sk, nqb, nkb)

    if dq_paired:
        def row_of(b, p, t):
            return _paired_qi_kj(p, t, nqb)[0]

        def col_of(b, p, t):
            return _paired_qi_kj(p, t, nqb)[1]

        dq_grid = (bh, nqb // 2, nqb + 1)
    else:
        def row_of(b, i, j):
            return i

        def col_of(b, i, j):
            return j

        dq_grid = (bh, nqb, nkb)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          segmented=segmented, block_q=block_q,
                          block_k=block_k, seq_q=sq, seq_k=sk,
                          dropout=dropout, biased=biased,
                          paired_nq=nqb if dq_paired else None),
        grid=dq_grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, j: (b, row_of(b, i, j), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: kv_index(b, row_of(b, i, j),
                                                  col_of(b, i, j))),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: kv_index(b, row_of(b, i, j),
                                                  col_of(b, i, j))),
            pl.BlockSpec((1, block_q, d),
                         lambda b, i, j: (b, row_of(b, i, j), 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j: (b, 0, row_of(b, i, j))),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j: (b, 0, row_of(b, i, j))),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j: (b, 0, row_of(b, i, j))),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b, 0, col_of(b, i, j))),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j, _h=h: (b // _h, 0,
                                                col_of(b, i, j))),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, i, j: (b, row_of(b, i, j), 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(q, k, v, do, lse, delta, seg_q, seg_k, seed, bias)

    # dk/dv are emitted per KV head ([B*HK, Sk, D]): for GQA (rep > 1) the
    # last grid axis streams rep * num_q_blocks steps — every query head of
    # the group — and the group sum happens in the accumulation scratch, so
    # no rep-times dk/dv ever hits HBM (true zero-copy KV in backward too).
    # rep == 1 keeps identity index maps: the div/mod maps of the grouped
    # path cost ~20% step time on the dense bench (Mosaic prefetch).
    nq_blocks = sq // block_q
    bhk = b_ * hk

    dkv_paired = rep == 1 and dq_paired
    if dkv_paired:
        # Column pairing (causal, sq == sk, dense heads): grid
        # (bhk, nq/2, nq+1) never fetches a masked query block.
        def q_head(bkv, t):
            return bkv

        def q_index(b, j, t):
            return (b, _paired_kj_qi(j, t, nq_blocks)[1], 0)

        def stat_index(b, j, t):
            return (b, 0, _paired_kj_qi(j, t, nq_blocks)[1])

        def dkv_col(b, j, t):
            return (b, _paired_kj_qi(j, t, nq_blocks)[0], 0)

        def segk_index(b, j, t):
            return (q_head(b, t), 0, _paired_kj_qi(j, t, nq_blocks)[0])

        dkv_grid = (bhk, nq_blocks // 2, nq_blocks + 1)
    elif rep == 1:
        def q_head(bkv, t):
            return bkv

        def q_index(b, j, t):
            return (b, t, 0)

        def stat_index(b, j, t):
            return (b, 0, t)

        def dkv_col(b, j, t):
            return (b, j, 0)

        def segk_index(b, j, t):
            return (q_head(b, t), 0, j)

        dkv_grid = (bhk, sk // block_k, rep * nq_blocks)
    else:
        def q_head(bkv, t):
            # flat query-head row for grid coords (kv-head bkv, step t)
            return (bkv // hk) * h + (bkv % hk) * rep + t // nq_blocks

        def q_index(b, j, t):
            return (q_head(b, t), t % nq_blocks, 0)

        def stat_index(b, j, t):
            return (q_head(b, t), 0, t % nq_blocks)

        def dkv_col(b, j, t):
            return (b, j, 0)

        def segk_index(b, j, t):
            return (q_head(b, t), 0, j)

        dkv_grid = (bhk, sk // block_k, rep * nq_blocks)

    def q_spec(width):
        return pl.BlockSpec((1, width, d), q_index)

    def stat_spec():
        return pl.BlockSpec((1, 1, block_q), stat_index)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          segmented=segmented, block_q=block_q,
                          block_k=block_k, seq_q=sq, seq_k=sk,
                          num_q_blocks=nq_blocks,
                          paired_nq=nq_blocks if dkv_paired else None,
                          dropout=dropout, biased=biased,
                          gqa_dims=(h, hk, rep)),
        grid=dkv_grid,
        in_specs=[
            pl.BlockSpec((1, block_k, d), dkv_col),
            pl.BlockSpec((1, block_k, d), dkv_col),
            q_spec(block_q),
            q_spec(block_q),
            stat_spec(),
            stat_spec(),
            stat_spec(),
            pl.BlockSpec((1, 1, block_k), segk_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, t, _hk=hk: (b // _hk, 0,
                                                  dkv_col(b, j, t)[1])),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), dkv_col),
            pl.BlockSpec((1, block_k, d), dkv_col),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(k, v, q, do, lse, delta, seg_q, seg_k, seed, bias)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper, [B, S, H, D] public layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _flash_bhsd(q, k, v, seg_q, seg_k, seed, bias, scale, causal, block_q,
                block_k, num_heads, dropout):
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k, num_heads,
                seg_q, seg_k, dropout=dropout, seed=seed, bias=bias)
    return o


def _flash_fwd_rule(q, k, v, seg_q, seg_k, seed, bias, scale, causal,
                    block_q, block_k, num_heads, dropout):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k, num_heads,
                  seg_q, seg_k, dropout=dropout, seed=seed, bias=bias)
    # Residuals carry checkpoint names so a remat policy can elect to SAVE
    # them: without this, jax.checkpoint re-runs the forward kernel inside
    # the backward (~0.96 ms/layer at the 1.3B shape) just to regenerate
    # (o, lse). See RecomputePolicy.DOTS_AND_FLASH.
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse, seg_q, seg_k, seed, bias)


def _flash_bwd_rule(scale, causal, block_q, block_k, num_heads, dropout,
                    res, do):
    q, k, v, o, lse, seg_q, seg_k, seed, bias = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                      num_heads, seg_q, seg_k, dropout=dropout, seed=seed,
                      bias=bias)
    # the additive key bias is a mask, not a trained parameter: no cotangent
    return dq, dk, dv, None, None, None, None


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bhsd_lse(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k,
                    num_heads):
    """Like _flash_bhsd but returns (o, lse [BH, 1, Sq] fp32) and is
    differentiable in BOTH outputs — the lse cotangent feeds ring-attention
    merges (distributed/context_parallel.py). No dropout (CP forbids it)."""
    return _fwd(q, k, v, scale, causal, block_q, block_k, num_heads,
                seg_q, seg_k)


def _flash_lse_fwd_rule(q, k, v, seg_q, seg_k, scale, causal, block_q,
                        block_k, num_heads):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k, num_heads,
                  seg_q, seg_k)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, o, lse, seg_q, seg_k)


def _flash_lse_bwd_rule(scale, causal, block_q, block_k, num_heads, res, ct):
    do, dlse = ct
    q, k, v, o, lse, seg_q, seg_k = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                      num_heads, seg_q, seg_k, dlse=dlse)
    return dq, dk, dv, None, None


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(query, key, value, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None):
    """[B, S, H, D] flash attention returning (o, lse [B, Sq, H] fp32).

    The blockwise-exact building block for ring context parallelism: two
    (o, lse) partials over disjoint key sets merge to the full softmax via
    lse' = logaddexp, o' = convex combination — and the custom VJP routes
    lse cotangents back through the kernels, so the merged result is
    differentiable end to end."""
    b, sq, h, d = query.shape
    sk = key.shape[1]
    auto_q, auto_k = _pick_blocks(sq, sk, d)
    block_q = block_q or auto_q
    block_k = block_k or auto_k
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        raise ValueError(
            f"flash_attention_with_lse needs seq lengths divisible by the "
            f"block sizes; got sq={sq}, sk={sk}")
    hk = key.shape[2]
    if hk != h and (hk == 0 or h % hk):
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {hk}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def to_bhsd(x, s, heads):
        return x.transpose(0, 2, 1, 3).reshape(b * heads, s, d)

    q = to_bhsd(query, sq, h)
    k = to_bhsd(key, sk, hk)
    v = to_bhsd(value, sk, hk)
    o, lse = _flash_bhsd_lse(q, k, v, None, None, float(scale), bool(causal),
                             block_q, block_k, h)
    o = o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, sq).transpose(0, 2, 1)
    return o, lse


def supported_shapes(query, key) -> bool:
    """True when the kernels handle these shapes (caller falls back else)."""
    sq, sk = query.shape[1], key.shape[1]
    d = query.shape[3]
    return sq % 128 == 0 and sk % 128 == 0 and d in (64, 128, 256)


def _free_mesh_axes():
    """(mesh, manual, free): the hybrid mesh, the axes an enclosing
    shard_map has already bound manual, and the >1 axes GSPMD still
    partitions here. ``free`` is empty on one device, without a hybrid
    mesh, or inside a fully manual region."""
    from ...distributed.context_parallel import _ambient_manual_axes
    from ...distributed.topology import get_hybrid_mesh
    mesh = get_hybrid_mesh()
    if mesh is None or mesh.size == 1:
        return mesh, (), ()
    manual = _ambient_manual_axes()
    return mesh, manual, tuple(a for a in mesh.axis_names
                               if a not in manual and mesh.shape[a] > 1)


def _flash_on_mesh(mesh, manual, free, query, key, value, causal, scale,
                   block_q, block_k, segment_ids, segment_ids_k, dropout,
                   dropout_seed, key_bias):
    """Run the kernel per shard under ``shard_map``. Mosaic kernels cannot
    be partitioned by GSPMD ("wrap the call in a shard_map"), so inside a
    multi-device step the call is made manual over every free mesh axis:
    batch over the data axes and heads over ``mp`` where they divide,
    replicated otherwise. Attention is independent per (batch, head), so
    the result is exact."""
    from jax.sharding import PartitionSpec as P
    from ...distributed.context_parallel import _inner_mesh
    b, sq, h, _ = query.shape
    sk, hk = key.shape[1], key.shape[2]

    def pick(axes, n):
        axes = tuple(a for a in axes if a in free)
        size = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and n % size == 0 else ()

    data = pick(("dp", "sharding"), b)
    heads = pick(("mp",), math.gcd(h, hk))
    d_ax = (data if len(data) > 1 else data[0]) if data else None
    h_ax = heads[0] if heads else None
    qkv_spec = P(d_ax, None, h_ax, None)
    row_spec = P(d_ax, None)
    n_shards = math.prod(mesh.shape[a] for a in data + heads)
    # one index per shard (a sharded arange, not lax.axis_index, which
    # does not verify when this region nests inside another manual axis):
    # folded into the dropout seed so shards draw different masks
    shard_ids = jnp.arange(n_shards, dtype=jnp.int32)
    ids_spec = P(data + heads if data or heads else None)
    if dropout > 0.0 and dropout_seed is None:
        from ...core.random import next_key
        dropout_seed = jax.random.randint(
            next_key(), (1,), 0, 2 ** 31 - 1, dtype=jnp.int32)
    seed = None if dropout_seed is None else \
        jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    if key_bias is not None:
        key_bias = jnp.broadcast_to(
            jnp.asarray(key_bias, jnp.float32).reshape(-1, sk), (b, sk))
    opt = {"segment_ids": (segment_ids, row_spec),
           "segment_ids_k": (segment_ids_k, row_spec),
           "dropout_seed": (seed, P()), "key_bias": (key_bias, row_spec)}
    names = [n for n, (v, _) in opt.items() if v is not None]

    def per_shard(q, k, v, ids, *rest):
        kw = dict(zip(names, rest))
        if "dropout_seed" in kw:
            kw["dropout_seed"] = kw["dropout_seed"] + ids[0] * 7919
        return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      block_q=block_q, block_k=block_k,
                                      dropout=dropout, **kw)

    fn = jax.shard_map(
        per_shard, mesh=_inner_mesh(mesh),
        in_specs=(qkv_spec, qkv_spec, qkv_spec, ids_spec,
                  *(opt[n][1] for n in names)),
        out_specs=qkv_spec, check_vma=False,
        # Mosaic wants EVERY mesh axis manual, the size-1 ones too
        axis_names=set(mesh.axis_names) - set(manual))
    return fn(query, key, value, shard_ids, *(opt[n][0] for n in names))


def flash_attention_pallas(query, key, value, causal: bool = False,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           segment_ids=None, segment_ids_k=None,
                           dropout: float = 0.0, dropout_seed=None,
                           key_bias=None, causal_block: int = 1):
    """[B, S, H, D] flash attention via Pallas. Differentiable (but for
    ``causal_block > 1``, the block-causal mask ``key <= query |
    (causal_block - 1)`` of generation by diffusion over blocks, which runs
    the forward alone).

    Block sizes default to the autotuned table in ``_pick_blocks``; pass
    explicit ``block_q``/``block_k`` to override. Grouped-query attention
    (kv heads dividing query heads) reads shared KV tiles through the
    BlockSpec index map — no repeat materialization. ``segment_ids``
    ([B, Sq] int32) enables packed-varlen attention: tokens attend only
    keys with an equal segment id (the TPU-native form of
    flash_attn_unpadded — static shapes, sequences packed along S).
    ``segment_ids_k`` ([B, Sk]) defaults to ``segment_ids``
    (self-attention packing). Under a multi-device hybrid mesh the call
    runs per shard (``_flash_on_mesh``)."""
    mesh, manual, free = _free_mesh_axes()
    if free and causal_block == 1:
        return _flash_on_mesh(mesh, manual, free, query, key, value, causal,
                              scale, block_q, block_k, segment_ids,
                              segment_ids_k, dropout, dropout_seed, key_bias)
    b, sq, h, d = query.shape
    sk = key.shape[1]
    hk = key.shape[2]
    dv = value.shape[3]
    # Head-packed fast path for d=64 dense-head shapes (VERDICT r4 #3):
    # G heads per program on the lane axis — G-fold fewer programs, full-
    # lane DMAs. Skipped when the caller pins blocks (kernel sweeps/tests
    # target a specific grid of the unpacked kernel).
    if (block_q is None and block_k is None and d == 64 and dv == d
            and hk == h and causal_block == 1
            and sq % 128 == 0 and sk % 128 == 0
            and int(_flags.flag("flash_head_pack"))):
        from .flash_attention_packed import (flash_attention_packed,
                                             pack_group)
        if pack_group(h):
            return flash_attention_packed(
                query, key, value, causal=causal, scale=scale,
                segment_ids=segment_ids, segment_ids_k=segment_ids_k,
                dropout=dropout, dropout_seed=dropout_seed,
                key_bias=key_bias)
    auto_q, auto_k = _pick_blocks(sq, sk, d)
    block_q = block_q or auto_q
    block_k = block_k or auto_k
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        raise ValueError(
            f"flash_attention_pallas needs seq lengths divisible by the "
            f"block sizes; got sq={sq}, sk={sk} (use supported_shapes())")
    if hk != h and (hk == 0 or h % hk):
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {hk} "
            f"(grouped-query)")
    if _flags.flag("static_analysis") != "off":
        # TPU-constraint pre-check of the chosen block config (P0xx rules)
        from ...analysis import pallas_check as _pc
        for _bwd in (False, True):
            _pc.enforce(_pc.spec_for_flash(sq, sk, d, block_q, block_k,
                                           query.dtype, bwd=_bwd),
                        where="flash_attention_pallas")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def to_bhsd(x, s, heads):
        return x.transpose(0, 2, 1, 3).reshape(b * heads, s, x.shape[3])

    # Grouped-query KV stays [B*HK, S, D]: the kernels' BlockSpec index map
    # routes each query head to its shared KV tile (no repeat materialized).
    q = to_bhsd(query, sq, h)
    k = to_bhsd(key, sk, hk)
    v = to_bhsd(value, sk, hk)
    seg_q = seg_k = None
    if segment_ids is not None:
        def per_head(seg, s, what):
            from ...analysis._jaxpr_utils import fmt_shape
            seg = jnp.asarray(seg, jnp.int32)
            if seg.shape != (b, s):
                raise ValueError(
                    f"{what} must be [batch, seq] = {fmt_shape((b, s))}; "
                    f"got {fmt_shape(seg.shape)}")
            return jnp.repeat(seg[:, None, :], h,
                              axis=1).reshape(b * h, 1, s)
        seg_q = per_head(segment_ids, sq, "segment_ids")
        seg_k = seg_q if segment_ids_k is None and sq == sk else \
            per_head(segment_ids_k if segment_ids_k is not None
                     else segment_ids, sk, "segment_ids_k")
    if dropout > 0.0:
        if dropout_seed is None:
            from ...core.random import next_key
            dropout_seed = jax.random.randint(
                next_key(), (1,), 0, 2 ** 31 - 1, dtype=jnp.int32)
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    bias = None
    if key_bias is not None:
        bias = jnp.asarray(key_bias, jnp.float32).reshape(b, 1, sk)
    if dv != d or causal_block > 1:
        # values of another head size than the keys, or a block-causal mask:
        # the forward alone (the backward kernels take one size and the
        # causal mask), so no gradient and no dropout
        if dropout > 0.0:
            raise ValueError(
                f"flash_attention_pallas: values of head size {dv} beside "
                f"keys of {d}, or causal_block {causal_block}, run forward "
                "only, without dropout")
        if causal_block > 1 and (not causal or min(block_q, sq) % causal_block
                                 or (sk - sq) % causal_block):
            raise ValueError(
                f"causal_block {causal_block} needs causal=True, tiles of "
                f"whole blocks (block_q {block_q}) and sk - sq a multiple")
        o, _ = _fwd(q, k, v, float(scale), bool(causal), block_q, block_k, h,
                    seg_q, seg_k, 0.0, seed, bias, causal_block)
    else:
        o = _flash_bhsd(q, k, v, seg_q, seg_k, seed, bias, float(scale),
                        bool(causal), block_q, block_k, h, float(dropout))
    return o.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
