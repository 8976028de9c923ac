"""Head-packed flash attention for small head dims (d=64) on TPU.

At d=64 every q/k/v tile is 64 lanes wide — half of the 128-lane VREG/MXU
width — and at encoder shapes (S=512, d=64) the per-program MXU work is a
few microseconds, so the plain per-head grid (one program per (batch,
head, q-block, k-block)) is dominated by program-dispatch and half-lane
DMA overhead, not FLOPs. This kernel packs G heads per program on the
LANE axis: arrays are laid out [B*H/G, S, G*64] (a pure reshape — head
features are already lane-contiguous in [B, S, H, 64]), the grid shrinks
by G, every DMA moves full 128-lane tiles, and the per-head dots are
static lane slices of the packed tile. The MXU pass count is unchanged
(a [bq,64]x[64,bk] dot costs the same passes as [bq,128]x[128,bk] — the
contraction is padded to the 128-deep systolic array either way; that
halved FLOP rate is the architectural floor for d=64 and no packing
scheme beats it), so all the win is dispatch + bandwidth + layout, which
is exactly what dominates at these shapes.

Reference parity: ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:324``
serves all head dims at full tensor-core rate (16-deep MACs); this is the
TPU-shaped answer to the same requirement. Dropout positions hash
identically to ``flash_attention.dropout_keep_dense`` (flat query-head
index b*H + h), so packed, unpacked, and dense-mirror paths agree bit-
for-bit under a fixed seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (NEG_INF, _causal_mask, _dot, _dropout_keepf)

__all__ = ["flash_attention_packed", "pack_group"]

HEAD_D = 64  # the packed path exists for exactly this head dim
MAX_PACK_LANES = 1024  # cap G*64 so tiles stay comfortably in VMEM


def pack_group(num_heads: int) -> int:
    """Largest even divisor of num_heads whose packed width fits the lane
    cap (even keeps every slice 128-aligned at least every other head)."""
    best = 0
    for g in range(2, num_heads + 1, 2):
        if num_heads % g == 0 and g * HEAD_D <= MAX_PACK_LANES:
            best = g
    return best


def _pick_blocks_packed(sq: int, sk: int, dp: int, bwd: bool = False):
    """(block_q, block_k) for the packed tile width dp = G*64. The G-way
    unrolled head loop keeps several [bq, bk] f32 temporaries live, and
    Mosaic's scoped-vmem stack is 16 MB — the backward kernels (5 live
    temporaries per head vs the forward's 2) need smaller score tiles, so
    bwd caps at 256-square. The autotune cache overrides when populated
    (key class flash_packed / flash_packed_bwd)."""
    from .autotune import get_cache
    hit = get_cache().get("flash_packed" + ("_bwd" if bwd else ""),
                          f"sq{sq}_sk{sk}_dp{dp}")
    if hit:
        tq, tk = tuple(hit)
        return min(tq, sq), min(tk, sk)
    # on-chip sweep at B64 S512 H12, fwd+bwd, device time, only configs
    # that pass the numeric guard: bwd 256x512 5.20 ms vs 256x256 5.91 /
    # 512x256 6.05; 512x512 overflows the 16MB scoped-vmem stack (the
    # G-way unrolled head loop keeps ~5 [bq,bk] f32 temporaries live).
    if bwd:
        cq, ck = (256, 512) if dp <= 768 else (128, 256)
    else:
        # 512-square q tiles overflow the stack in the G=12 direct form
        # (in-graph, with the segment/bias dummies); 256x512 fits and
        # keeps block_k == seq for the scratch-free single-k-block path.
        cq, ck = (256, 512) if dp <= 768 else (256, 256)

    def fit(cap, s):
        b = min(cap, s)
        while b > 128 and s % b:
            b -= 128
        return b

    return fit(cq, sq), fit(ck, sk)


def _seg_mask_b(s, segq_ref, segk_ref):
    seg_q = segq_ref[0].T        # [bq, 1]
    seg_k = segk_ref[0]          # [1, bk]
    return jnp.where(seg_q == seg_k, s, NEG_INF)


def _flat_head(bg, hg, g_pack, h, num_heads):
    """Flat query-head row (b*H + head) for the dropout hash: packed row
    bg = b*HG + g holds original heads g*G .. g*G+G-1."""
    return (bg // hg) * num_heads + (bg % hg) * g_pack + h


def _fwd_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, seed_ref, bias_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, segmented, block_q, block_k, seq_q, seq_k,
                g_pack, hg, num_heads, dropout=0.0, biased=False):
    bg = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    offset = seq_k - seq_q

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    in_band = jnp.asarray(True) if not causal \
        else kj * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(in_band)
    def _step():
        qp = q_ref[0]            # [bq, G*64]
        kp = k_ref[0]            # [bk, G*64]
        vp = v_ref[0]
        for h in range(g_pack):
            sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
            s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, offset)
            if segmented:
                s = _seg_mask_b(s, segq_ref, segk_ref)
            if biased:
                s = s + bias_ref[0]
            hsl = slice(h, h + 1)
            m_prev = m_scr[:, hsl]
            l_prev = l_scr[:, hsl]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[:, hsl] = m_new
            l_scr[:, hsl] = l_prev * alpha + jnp.sum(p, axis=1,
                                                     keepdims=True)
            pv = p
            if dropout > 0.0:
                pv = p * _dropout_keepf(
                    p.shape, _flat_head(bg, hg, g_pack, h, num_heads),
                    qi, kj, block_q, block_k, seq_q, seq_k,
                    seed_ref[0], dropout)
            acc_scr[:, sl] = acc_scr[:, sl] * alpha \
                + _dot(pv.astype(vp.dtype), vp[:, sl], ((1,), (0,)))

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)          # [bq, G]
        # acc is [bq, G*64]; divide each head's 64 lanes by its l column
        # (per-head slice stores — Mosaic has no [bq,G]->[bq,G*64] repeat)
        for h in range(g_pack):
            sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
            o_ref[0, :, sl] = (acc_scr[:, sl]
                               / l[:, h:h + 1]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)        # [bq, G]


def _fwd_kernel_direct(q_ref, k_ref, v_ref, segq_ref, segk_ref, seed_ref,
                       bias_ref, o_ref, lse_ref,
                       *, scale, causal, segmented, block_q, block_k,
                       seq_q, seq_k, g_pack, hg, num_heads, dropout=0.0,
                       biased=False):
    """Single-k-block specialization (block_k >= seq_k): plain per-head
    softmax, no online-max scratch, no narrow-lane m/l read-modify-write —
    measured 2.2x faster than the streamed form at B64 S512 G12 (the
    common encoder shape puts the WHOLE key sequence in one tile)."""
    bg = pl.program_id(0)
    qi = pl.program_id(1)
    offset = seq_k - seq_q
    qp = q_ref[0]
    kp = k_ref[0]
    vp = v_ref[0]
    for h in range(g_pack):
        sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
        s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
        if causal:
            s = _causal_mask(s, qi, 0, block_q, block_k, offset)
        if segmented:
            s = _seg_mask_b(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m) * (s > NEG_INF / 2)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        pv = p
        if dropout > 0.0:
            pv = p * _dropout_keepf(
                p.shape, _flat_head(bg, hg, g_pack, h, num_heads), qi, 0,
                block_q, block_k, seq_q, seq_k, seed_ref[0], dropout)
        o = _dot(pv.astype(vp.dtype), vp[:, sl], ((1,), (0,)))
        o_ref[0, :, sl] = (o / l).astype(o_ref.dtype)
        lse_ref[0, :, h:h + 1] = m + jnp.log(l)


def _fwd(q, k, v, scale, causal, block_q, block_k, g_pack, num_heads,
         seg_q=None, seg_k=None, dropout=0.0, seed=None, bias=None):
    """q/k/v: [B*HG, S, G*64] packed; seg_q/k: [B, 1, S] int32 or None;
    bias: [B, 1, Sk] f32 or None -> (o, lse [B*HG, G, Sq] f32)."""
    bhg, sq, dp = q.shape
    sk = k.shape[1]
    hg = num_heads // g_pack
    b = bhg // hg
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    segmented = seg_q is not None
    if not segmented:
        seg_q = jnp.zeros((b, 1, sq), jnp.int32)
        seg_k = jnp.zeros((b, 1, sk), jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    biased = bias is not None
    if not biased:
        bias = jnp.zeros((b, 1, sk), jnp.float32)
    nq, nk = sq // block_q, sk // block_k
    cost = pl.CostEstimate(
        flops=4 * bhg * g_pack * sq * sk * HEAD_D
        // (2 if causal else 1),
        bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
        transcendentals=bhg * g_pack * sq * sk,
    )
    out_shape = [
        jax.ShapeDtypeStruct((bhg, sq, dp), q.dtype),
        jax.ShapeDtypeStruct((bhg, sq, g_pack), jnp.float32),
    ]
    if nk == 1:
        kern = functools.partial(
            _fwd_kernel_direct, scale=scale, causal=causal,
            segmented=segmented, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk, g_pack=g_pack, hg=hg,
            num_heads=num_heads, dropout=dropout, biased=biased)
        o, lse = pl.pallas_call(
            kern,
            grid=(bhg, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, dp), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, i)),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dp), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_q, g_pack),
                             lambda b_, i: (b_, i, 0)),
            ],
            out_shape=out_shape,
            cost_estimate=cost,
        )(q, k, v, seg_q, seg_k, seed, bias)
        return o, lse
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, segmented=segmented,
        block_q=block_q, block_k=block_k, seq_q=sq, seq_k=sk,
        g_pack=g_pack, hg=hg, num_heads=num_heads, dropout=dropout,
        biased=biased)
    o, lse = pl.pallas_call(
        kern,
        grid=(bhg, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, block_k, dp), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, i, j, _hg=hg: (b_ // _hg, 0, i)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b_, i, j, _hg=hg: (b_ // _hg, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b_, i, j, _hg=hg: (b_ // _hg, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, g_pack), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, g_pack), jnp.float32),
            pltpu.VMEM((block_q, g_pack), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        cost_estimate=cost,
    )(q, k, v, seg_q, seg_k, seed, bias)
    return o, lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   segq_ref, segk_ref, seed_ref, bias_ref, dq_ref, dq_scr,
                   *, scale, causal, segmented, block_q, block_k,
                   seq_q, seq_k, g_pack, hg, num_heads, dropout=0.0,
                   biased=False):
    bg = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    offset = seq_k - seq_q

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    in_band = jnp.asarray(True) if not causal \
        else kj * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(in_band)
    def _step():
        qp = q_ref[0]
        kp = k_ref[0]
        vp = v_ref[0]
        dop = do_ref[0]
        for h in range(g_pack):
            sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
            lse = lse_ref[0][:, h:h + 1]        # [bq, 1]
            delta = delta_ref[0][:, h:h + 1]
            s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, offset)
            if segmented:
                s = _seg_mask_b(s, segq_ref, segk_ref)
            if biased:
                s = s + bias_ref[0]
            p = jnp.exp(s - lse) * (s > NEG_INF / 2)
            dp = _dot(dop[:, sl], vp[:, sl], ((1,), (1,)))
            if dropout > 0.0:
                dp = dp * _dropout_keepf(
                    p.shape, _flat_head(bg, hg, g_pack, h, num_heads),
                    qi, kj, block_q, block_k, seq_q, seq_k,
                    seed_ref[0], dropout)
            ds = (p * (dp - delta) * scale).astype(kp.dtype)
            dq_scr[:, sl] = dq_scr[:, sl] + _dot(ds, kp[:, sl],
                                                 ((1,), (0,)))

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    segq_ref, segk_ref, seed_ref, bias_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, scale, causal, segmented, block_q, block_k,
                    seq_q, seq_k, g_pack, hg, num_heads, dropout=0.0,
                    biased=False):
    bg = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    in_band = jnp.asarray(True) if not causal \
        else (qi + 1) * block_q - 1 + offset >= kj * block_k

    @pl.when(in_band)
    def _step():
        kp = k_ref[0]
        vp = v_ref[0]
        qp = q_ref[0]
        dop = do_ref[0]
        for h in range(g_pack):
            sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
            lse = lse_ref[0][:, h:h + 1]
            delta = delta_ref[0][:, h:h + 1]
            s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, offset)
            if segmented:
                s = _seg_mask_b(s, segq_ref, segk_ref)
            if biased:
                s = s + bias_ref[0]
            p = jnp.exp(s - lse) * (s > NEG_INF / 2)
            pv = p
            dp = _dot(dop[:, sl], vp[:, sl], ((1,), (1,)))
            if dropout > 0.0:
                keepf = _dropout_keepf(
                    p.shape, _flat_head(bg, hg, g_pack, h, num_heads),
                    qi, kj, block_q, block_k, seq_q, seq_k,
                    seed_ref[0], dropout)
                pv = p * keepf
                dp = dp * keepf
            dv_scr[:, sl] = dv_scr[:, sl] + _dot(
                pv.astype(dop.dtype), dop[:, sl], ((0,), (0,)))
            ds = (p * (dp - delta) * scale).astype(qp.dtype)
            dk_scr[:, sl] = dk_scr[:, sl] + _dot(ds, qp[:, sl],
                                                 ((0,), (0,)))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dkv_kernel_direct(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                           segq_ref, segk_ref, seed_ref, bias_ref, dk_ref,
                           dv_ref,
                           *, scale, causal, segmented, block_q, block_k,
                           seq_q, seq_k, g_pack, hg, num_heads,
                           dropout=0.0, biased=False):
    """Single-q-block dk/dv: the whole query sequence sits in one tile."""
    bg = pl.program_id(0)
    kj = pl.program_id(1)
    offset = seq_k - seq_q
    kp = k_ref[0]
    vp = v_ref[0]
    qp = q_ref[0]
    dop = do_ref[0]
    for h in range(g_pack):
        sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
        lse = lse_ref[0][:, h:h + 1]
        delta = delta_ref[0][:, h:h + 1]
        s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
        if causal:
            s = _causal_mask(s, 0, kj, block_q, block_k, offset)
        if segmented:
            s = _seg_mask_b(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]
        p = jnp.exp(s - lse) * (s > NEG_INF / 2)
        pv = p
        dp = _dot(dop[:, sl], vp[:, sl], ((1,), (1,)))
        if dropout > 0.0:
            keepf = _dropout_keepf(
                p.shape, _flat_head(bg, hg, g_pack, h, num_heads), 0, kj,
                block_q, block_k, seq_q, seq_k, seed_ref[0], dropout)
            pv = p * keepf
            dp = dp * keepf
        dv_ref[0, :, sl] = _dot(pv.astype(dop.dtype), dop[:, sl],
                                ((0,), (0,))).astype(dv_ref.dtype)
        ds = (p * (dp - delta) * scale).astype(qp.dtype)
        dk_ref[0, :, sl] = _dot(ds, qp[:, sl],
                                ((0,), (0,))).astype(dk_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      segq_ref, segk_ref, seed_ref, bias_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                      *, scale, causal, segmented, block_q, block_k,
                      seq_q, seq_k, g_pack, hg, num_heads, dropout=0.0,
                      biased=False):
    """Fused dq+dkv for the single-k-block regime (block_k >= seq_k).

    The r4 fused-backward attempt was rejected because dq and dk/dv have
    conflicting reduction axes — accumulating one of them meant HBM
    read-modify-write across grid steps, unsound under Mosaic's async
    output windows. With the WHOLE key sequence in the tile that conflict
    disappears: dq is complete within one program (its k-reduction is the
    in-tile dot), and dk/dv accumulate across the streamed q-blocks in
    VMEM scratch — the one (s, p) recompute serves all three gradients
    (5 dot-sets per head vs 3+4 in the split kernels, exp once vs twice,
    q/do DMA'd once vs twice)."""
    bg = pl.program_id(0)
    qi = pl.program_id(1)
    nq = pl.num_programs(1)
    offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qp = q_ref[0]
    kp = k_ref[0]
    vp = v_ref[0]
    dop = do_ref[0]
    for h in range(g_pack):
        sl = slice(h * HEAD_D, (h + 1) * HEAD_D)
        lse = lse_ref[0][:, h:h + 1]
        delta = delta_ref[0][:, h:h + 1]
        s = _dot(qp[:, sl], kp[:, sl], ((1,), (1,))) * scale
        if causal:
            s = _causal_mask(s, qi, 0, block_q, block_k, offset)
        if segmented:
            s = _seg_mask_b(s, segq_ref, segk_ref)
        if biased:
            s = s + bias_ref[0]
        p = jnp.exp(s - lse) * (s > NEG_INF / 2)
        pv = p
        dp = _dot(dop[:, sl], vp[:, sl], ((1,), (1,)))
        if dropout > 0.0:
            keepf = _dropout_keepf(
                p.shape, _flat_head(bg, hg, g_pack, h, num_heads), qi, 0,
                block_q, block_k, seq_q, seq_k, seed_ref[0], dropout)
            pv = p * keepf
            dp = dp * keepf
        ds = (p * (dp - delta) * scale).astype(kp.dtype)
        dq_ref[0, :, sl] = _dot(ds, kp[:, sl],
                                ((1,), (0,))).astype(dq_ref.dtype)
        dv_scr[:, sl] = dv_scr[:, sl] + _dot(
            pv.astype(dop.dtype), dop[:, sl], ((0,), (0,)))
        dk_scr[:, sl] = dk_scr[:, sl] + _dot(ds, qp[:, sl], ((0,), (0,)))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k, g_pack,
         num_heads, seg_q=None, seg_k=None, dropout=0.0, seed=None,
         bias=None):
    bhg, sq, dp = q.shape
    sk = k.shape[1]
    hg = num_heads // g_pack
    b = bhg // hg
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    segmented = seg_q is not None
    if not segmented:
        seg_q = jnp.zeros((b, 1, sq), jnp.int32)
        seg_k = jnp.zeros((b, 1, sk), jnp.int32)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    biased = bias is not None
    if not biased:
        bias = jnp.zeros((b, 1, sk), jnp.float32)
    # per-head delta = rowsum(dO * O): [B*HG, Sq, G] matching the lse layout
    prod = (do.astype(jnp.float32) * o.astype(jnp.float32))
    delta = prod.reshape(bhg, sq, g_pack, HEAD_D).sum(-1)
    nq, nk = sq // block_q, sk // block_k

    def batch_of(b_, i, j, _hg=hg):
        return b_ // _hg

    kw = dict(scale=scale, causal=causal, segmented=segmented,
              seq_q=sq, seq_k=sk, g_pack=g_pack, hg=hg,
              num_heads=num_heads, dropout=dropout, biased=biased)

    if nk == 1:
        # fused dq+dkv: one (s, p) recompute serves all three grads
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, block_q=block_q,
                              block_k=block_k, **kw),
            grid=(bhg, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, dp), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_q, dp), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_q, g_pack),
                             lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_q, g_pack),
                             lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, i)),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, _hg=hg: (b_ // _hg, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dp), lambda b_, i: (b_, i, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
                pl.BlockSpec((1, block_k, dp), lambda b_, i: (b_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bhg, sq, dp), q.dtype),
                jax.ShapeDtypeStruct((bhg, sk, dp), k.dtype),
                jax.ShapeDtypeStruct((bhg, sk, dp), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, dp), jnp.float32),
                pltpu.VMEM((block_k, dp), jnp.float32),
            ],
        )(q, k, v, do, lse, delta, seg_q, seg_k, seed, bias)
        return dq, dk, dv
    if nk > 1:  # streamed dq over key blocks
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, block_q=block_q,
                              block_k=block_k, **kw),
            grid=(bhg, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, dp),
                             lambda b_, i, j: (b_, i, 0)),
                pl.BlockSpec((1, block_k, dp),
                             lambda b_, i, j: (b_, j, 0)),
                pl.BlockSpec((1, block_k, dp),
                             lambda b_, i, j: (b_, j, 0)),
                pl.BlockSpec((1, block_q, dp),
                             lambda b_, i, j: (b_, i, 0)),
                pl.BlockSpec((1, block_q, g_pack),
                             lambda b_, i, j: (b_, i, 0)),
                pl.BlockSpec((1, block_q, g_pack),
                             lambda b_, i, j: (b_, i, 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b_, i, j: (batch_of(b_, i, j), 0, i)),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, j: (batch_of(b_, i, j), 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, block_k),
                             lambda b_, i, j: (batch_of(b_, i, j), 0, j)),
            ],
            out_specs=pl.BlockSpec((1, block_q, dp),
                                   lambda b_, i, j: (b_, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bhg, sq, dp), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        )(q, k, v, do, lse, delta, seg_q, seg_k, seed, bias)

    # dkv mirrors the dq tiling: its streamed axis is q, so it gets the
    # SMALL tile on q and the large one on k (block_k x block_q swapped);
    # unmirrored when sq != sk makes the swap non-dividing.
    kq, kk = block_k, block_q
    if sq % min(kq, sq) or sk % min(kk, sk):
        kq, kk = block_q, block_k
    nkv_q, nkv_k = sq // min(kq, sq), sk // min(kk, sk)
    kq, kk = min(kq, sq), min(kk, sk)
    dkv_out = [
        jax.ShapeDtypeStruct((bhg, sk, dp), k.dtype),
        jax.ShapeDtypeStruct((bhg, sk, dp), v.dtype),
    ]
    if nkv_q == 1:
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel_direct, block_q=kq,
                              block_k=kk, **kw),
            grid=(bhg, nkv_k),
            in_specs=[
                pl.BlockSpec((1, kk, dp), lambda b_, j: (b_, j, 0)),
                pl.BlockSpec((1, kk, dp), lambda b_, j: (b_, j, 0)),
                pl.BlockSpec((1, kq, dp), lambda b_, j: (b_, 0, 0)),
                pl.BlockSpec((1, kq, dp), lambda b_, j: (b_, 0, 0)),
                pl.BlockSpec((1, kq, g_pack), lambda b_, j: (b_, 0, 0)),
                pl.BlockSpec((1, kq, g_pack), lambda b_, j: (b_, 0, 0)),
                pl.BlockSpec((1, 1, kq),
                             lambda b_, j, _hg=hg: (b_ // _hg, 0, 0)),
                pl.BlockSpec((1, 1, kk),
                             lambda b_, j, _hg=hg: (b_ // _hg, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, kk),
                             lambda b_, j, _hg=hg: (b_ // _hg, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, kk, dp), lambda b_, j: (b_, j, 0)),
                pl.BlockSpec((1, kk, dp), lambda b_, j: (b_, j, 0)),
            ],
            out_shape=dkv_out,
        )(k, v, q, do, lse, delta, seg_q, seg_k, seed, bias)
    else:
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, block_q=kq, block_k=kk,
                              **kw),
            grid=(bhg, nkv_k, nkv_q),
            in_specs=[
                pl.BlockSpec((1, kk, dp), lambda b_, j, t: (b_, j, 0)),
                pl.BlockSpec((1, kk, dp), lambda b_, j, t: (b_, j, 0)),
                pl.BlockSpec((1, kq, dp), lambda b_, j, t: (b_, t, 0)),
                pl.BlockSpec((1, kq, dp), lambda b_, j, t: (b_, t, 0)),
                pl.BlockSpec((1, kq, g_pack), lambda b_, j, t: (b_, t, 0)),
                pl.BlockSpec((1, kq, g_pack), lambda b_, j, t: (b_, t, 0)),
                pl.BlockSpec((1, 1, kq),
                             lambda b_, j, t: (batch_of(b_, j, t), 0, t)),
                pl.BlockSpec((1, 1, kk),
                             lambda b_, j, t: (batch_of(b_, j, t), 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, kk),
                             lambda b_, j, t: (batch_of(b_, j, t), 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, kk, dp), lambda b_, j, t: (b_, j, 0)),
                pl.BlockSpec((1, kk, dp), lambda b_, j, t: (b_, j, 0)),
            ],
            out_shape=dkv_out,
            scratch_shapes=[
                pltpu.VMEM((kk, dp), jnp.float32),
                pltpu.VMEM((kk, dp), jnp.float32),
            ],
        )(k, v, q, do, lse, delta, seg_q, seg_k, seed, bias)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15))
def _flash_packed(q, k, v, seg_q, seg_k, seed, bias, scale, causal,
                  block_q, block_k, bwd_bq, bwd_bk, g_pack, num_heads,
                  dropout):
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k, g_pack, num_heads,
                seg_q, seg_k, dropout=dropout, seed=seed, bias=bias)
    return o


def _flash_packed_fwd(q, k, v, seg_q, seg_k, seed, bias, scale, causal,
                      block_q, block_k, bwd_bq, bwd_bk, g_pack, num_heads,
                      dropout):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k, g_pack,
                  num_heads, seg_q, seg_k, dropout=dropout, seed=seed,
                  bias=bias)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse, seg_q, seg_k, seed, bias)


def _flash_packed_bwd(scale, causal, block_q, block_k, bwd_bq, bwd_bk,
                      g_pack, num_heads, dropout, res, do):
    q, k, v, o, lse, seg_q, seg_k, seed, bias = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale, causal, bwd_bq, bwd_bk,
                      g_pack, num_heads, seg_q, seg_k, dropout=dropout,
                      seed=seed, bias=bias)
    return dq, dk, dv, None, None, None, None


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention_packed(query, key, value, causal=False, scale=None,
                           block_q=None, block_k=None, segment_ids=None,
                           segment_ids_k=None, dropout=0.0,
                           dropout_seed=None, key_bias=None,
                           g_pack=None):
    """[B, S, H, 64] flash attention with G heads packed per program.

    Drop-in equal to ``flash_attention_pallas`` for d=64 dense-head (MHA)
    shapes — same math, same dropout hash, same lse semantics — routed by
    the caller when the packing preconditions hold (d == 64, kv heads ==
    query heads, H divisible by an even group)."""
    import math as _math
    b, sq, h, d = query.shape
    if d != HEAD_D:
        raise ValueError(f"packed path is d=64 only; got {d}")
    sk = key.shape[1]
    if key.shape[2] != h:
        raise ValueError("packed path needs kv heads == query heads")
    g = g_pack or pack_group(h)
    if not g:
        raise ValueError(f"no even pack group divides {h} heads")
    hg = h // g
    auto_q, auto_k = _pick_blocks_packed(sq, sk, d * g)
    bwd_auto_q, bwd_auto_k = _pick_blocks_packed(sq, sk, d * g, bwd=True)
    # explicit caller blocks pin BOTH directions (sweep/test hook)
    bwd_bq = block_q or bwd_auto_q
    bwd_bk = block_k or bwd_auto_k
    block_q = block_q or auto_q
    block_k = block_k or auto_k
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        raise ValueError(
            f"packed flash needs seq lengths divisible by blocks; "
            f"sq={sq}, sk={sk}")
    from ...core import flags as _flags
    if _flags.flag("static_analysis") != "off":
        # Enforce the tuning folklore statically (P001/P004: the backward
        # score-tile VMEM budget that forced the 256-row cap) before
        # Mosaic hits it at compile time on hardware.
        from ...analysis import pallas_check as _pc
        _pc.enforce(_pc.spec_for_flash_packed(
            sq, sk, g * HEAD_D, block_q, block_k, g, query.dtype),
            where="flash_attention_packed")
        _pc.enforce(_pc.spec_for_flash_packed(
            sq, sk, g * HEAD_D, bwd_bq, bwd_bk, g, query.dtype, bwd=True),
            where="flash_attention_packed")
    scale = scale if scale is not None else 1.0 / _math.sqrt(d)

    def to_packed(x, s):
        # [B, S, H, 64] -> [B, S, HG, G*64] is a pure reshape (head
        # features are lane-contiguous); then one full-lane transpose.
        return (x.reshape(b, s, hg, g * HEAD_D)
                 .transpose(0, 2, 1, 3)
                 .reshape(b * hg, s, g * HEAD_D))

    q = to_packed(query, sq)
    k = to_packed(key, sk)
    v = to_packed(value, sk)
    seg_q = seg_k = None
    if segment_ids is not None:
        def as_seg(ids, s_, what):
            from ...analysis._jaxpr_utils import fmt_shape
            ids = jnp.asarray(ids, jnp.int32)
            if ids.shape != (b, s_):
                raise ValueError(
                    f"{what} must be [batch, seq] = {fmt_shape((b, s_))}; "
                    f"got {fmt_shape(ids.shape)}")
            return ids.reshape(b, 1, s_)
        seg_q = as_seg(segment_ids, sq, "segment_ids")
        sk_ids = segment_ids_k if segment_ids_k is not None else \
            (segment_ids if sq == sk else None)
        if sk_ids is None:
            raise ValueError("segment_ids_k required when sq != sk")
        seg_k = as_seg(sk_ids, sk, "segment_ids_k")
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
    o = _flash_packed(q, k, v, seg_q, seg_k, seed, bias, float(scale),
                      bool(causal), block_q, block_k, bwd_bq, bwd_bk, g, h,
                      float(dropout))
    return (o.reshape(b, hg, sq, g * HEAD_D)
             .transpose(0, 2, 1, 3)
             .reshape(b, sq, h, d))
