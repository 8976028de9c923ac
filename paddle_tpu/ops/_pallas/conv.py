"""Pallas TPU conv kernel family with in-kernel BN epilogues (fwd +
dgrad/wgrad) — the cuDNN-class fused conv library the reference keeps at
``paddle/phi/kernels/gpudnn/conv_kernel.cu`` + ``conv_cudnn_v7.h``.

Why this exists (VERDICT r5 missing #2): ResNet-50 is the repo's only
failing perf gate (0.773x vs the 0.9x north star) and PERF.md r5 proved
the remaining ~12 GB/step cannot come from graph restructuring — XLA
already fuses BN stats as conv-epilogue tuple outputs, so the bytes can
only move if a *kernel* changes the traffic. These kernels do, for the
byte-dominant ResNet shape classes:

- **1x1 conv as matmul** (``[N*H*W, Cin] @ [Cin, Cout]``) with the BN
  apply + ReLU of the *previous* layer fused as an in-kernel prologue and
  the per-channel (sum, sumsq) of the output accumulated in VMEM scratch
  as an epilogue: the normalized activation never round-trips HBM, and
  the next BN's stats are free.
- **NHWC 3x3 (stride 1 and 2)** via im2col-in-kernel block loads: the
  padded image rides VMEM once per batch index, each grid step assembles
  its nine shifted tap tiles in VMEM (never in HBM — the classic im2col
  blowup stays on-chip) and feeds the MXU; same prologue/epilogue hooks.
- The **dgrad/wgrad backward pair**: dgrad reuses the forward kernels on
  rotated taps (stride-2 via an outside dy dilation), wgrad accumulates
  ``a^T @ dy`` per tap in an f32 VMEM scratch across the grid, with the
  BN+ReLU prologue *recomputed in-kernel* from the raw input
  (flash-attention-style remat — only the pre-BN tensor is ever saved).

Routing: ``FLAGS_pallas_conv`` (default OFF: no measured win) swaps
these kernels into the deferred-BN units of ``nn/fused_conv_bn.py``;
unsupported shapes (groups, dilation, other kernel sizes, over-VMEM
configs) fall back to the lax path inside the same custom_vjp boundaries. On non-TPU backends
the kernels run in Pallas interpret mode, so the whole family is
CPU-verifiable (tier-1 parity tests in ``tests/test_pallas_conv.py``).

Block configs consult the persistent device-time autotune cache
(``ops/_pallas/autotune.py``; keys ``pallas_conv1x1`` / ``pallas_conv3x3``)
before the static divisor tables. Declared configurations are checked
against the TPU constraints (16MB scoped VMEM incl. im2col tiles,
(8,128) tiles, grid divisibility) by ``analysis/pallas_check.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import flags as _flags

__all__ = [
    "conv2d", "conv2d_fwd", "conv2d_dgrad", "conv2d_wgrad", "supports",
    "pallas_conv_enabled", "RESNET50_TOP3_SHAPES",
]

if "pallas_conv" not in _flags.get_flags():
    _flags.define_flag(
        "pallas_conv", 0,
        "route supported convs (1x1-as-matmul, NHWC 3x3 s1/s2) through "
        "the Pallas conv kernel family with in-kernel BN epilogues "
        "(default off until a measured win)")

# The three byte-dominant conv shape classes of the r5 ResNet-50 profile
# (tools/resnet_bytes.py, batch 256, bw-derived GB/step: the stage-1
# 56x56 activations dominate — the 1x1 reduce/expand pair around the
# bottleneck and the 3x3 workhorse). (kind, n, h, w, cin, cout, stride).
RESNET50_TOP3_SHAPES = (
    ("conv1x1", 256, 56, 56, 256, 64, 1),
    ("conv1x1", 256, 56, 56, 64, 256, 1),
    ("conv3x3", 256, 56, 56, 64, 64, 1),
)

_MM_BLOCKS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
_C3_BLOCKS = (16, 8, 4, 2, 1)


def _interpret_default() -> bool:
    """Real Mosaic on a TPU backend, interpreter everywhere else — the
    CPU-verifiability contract of the family."""
    return jax.default_backend() != "tpu"


# What the chip's compiler (libtpu 0.0.34, v5e) says to the 3x3 kernels:
# ``_c3_taps`` takes its tap tiles with a value-level lax.dynamic_slice
# (+ strided slice + row-merging reshape), which Mosaic does not lower.
# The 3x3 family has only ever run in interpret mode; on a TPU it is not
# routable (``supports``) until it is rewritten or deleted (ROADMAP A2/C2).
C3_MOSAIC_REFUSAL = ("the chip's compiler refuses the 3x3 kernels: "
                     "NotImplementedError: Unimplemented primitive in "
                     "Pallas TPU lowering for tc: dynamic_slice")


def pallas_conv_enabled() -> bool:
    return bool(_flags.flag("pallas_conv"))


def _tuned(kernel: str, key: str) -> Optional[int]:
    from .autotune import get_cache
    hit = get_cache().get(kernel, key)
    return int(hit) if hit else None


def _largest_divisor(n: int, candidates: Sequence[int]) -> int:
    for b in candidates:
        if n % b == 0:
            return b
    return 1


def _mm_key(m, cin, cout, dtype) -> str:
    return f"m{m}_ci{cin}_co{cout}_{jnp.dtype(dtype).name}"


def _c3_key(n, h, w, c, k, stride, dtype) -> str:
    return f"n{n}_h{h}_w{w}_c{c}_k{k}_s{stride}_{jnp.dtype(dtype).name}"


def _pick_block_m(m: int, cin: int, cout: int, dtype) -> int:
    hit = _tuned("pallas_conv1x1", _mm_key(m, cin, cout, dtype))
    if hit and m % hit == 0:
        return hit
    return _largest_divisor(m, _MM_BLOCKS)


def _pick_block_h(ho: int, n, h, w, c, k, stride, dtype) -> int:
    hit = _tuned("pallas_conv3x3", _c3_key(n, h, w, c, k, stride, dtype))
    if hit and ho % hit == 0:
        return hit
    return _largest_divisor(ho, _C3_BLOCKS)


def _enforce(spec, where: str):
    from ...analysis.pallas_check import enforce
    enforce(spec, where=where)


# ---------------------------------------------------------------------------
# 1x1-as-matmul kernels (fwd doubles as dgrad on transposed weights)
# ---------------------------------------------------------------------------

def _mm_kernel(x_ref, w_ref, scale_ref, shift_ref, y_ref, s_ref, ss_ref,
               s_scr, ss_scr, *, prologue: bool, act: str, stats: bool,
               nm: int):
    i = pl.program_id(1)  # row-block index (inner grid axis)
    xb = x_ref[0]
    if prologue:
        xb = xb * scale_ref[0].astype(xb.dtype) + \
            shift_ref[0].astype(xb.dtype)
        if act == "relu":
            xb = jnp.maximum(xb, 0)
    acc = lax.dot_general(xb, w_ref[0], (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    y_ref[0] = acc.astype(y_ref.dtype)
    if stats:
        @pl.when(i == 0)
        def _init():
            s_scr[...] = jnp.zeros_like(s_scr)
            ss_scr[...] = jnp.zeros_like(ss_scr)

        s_scr[...] += jnp.sum(acc, axis=0, keepdims=True)
        ss_scr[...] += jnp.sum(acc * acc, axis=0, keepdims=True)

        @pl.when(i == nm - 1)
        def _fin():
            s_ref[0] = s_scr[...]
            ss_ref[0] = ss_scr[...]
    else:
        @pl.when(i == nm - 1)
        def _fin0():
            s_ref[0] = jnp.zeros(s_ref.shape[1:], s_ref.dtype)
            ss_ref[0] = jnp.zeros(ss_ref.shape[1:], ss_ref.dtype)


def _mm(x2, w2, scale, shift, prologue: bool, act: str, stats: bool,
        block_m: int, interpret: bool):
    m, cin = x2.shape
    cout = w2.shape[1]
    block_m = min(block_m, m)
    nm = m // block_m
    if scale is None:
        scale = jnp.zeros((cin,), jnp.float32)
        shift = jnp.zeros((cin,), jnp.float32)
    kern = functools.partial(_mm_kernel, prologue=prologue, act=act,
                             stats=stats, nm=nm)
    y, s, ss = pl.pallas_call(
        kern,
        grid=(1, nm),  # trivial outer axis keeps the row loop innermost
        in_specs=[
            pl.BlockSpec((1, block_m, cin), lambda j, i: (0, i, 0)),
            pl.BlockSpec((1, cin, cout), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, cin), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, cin), lambda j, i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, cout), lambda j, i: (0, i, 0)),
            pl.BlockSpec((1, 1, cout), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, cout), lambda j, i: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m, cout), x2.dtype),
            jax.ShapeDtypeStruct((1, 1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, 1, cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, cout), jnp.float32),
            pltpu.VMEM((1, cout), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * m * cin * cout,
            bytes_accessed=(x2.size * x2.dtype.itemsize +
                            m * cout * x2.dtype.itemsize +
                            w2.size * w2.dtype.itemsize),
            transcendentals=0),
        interpret=interpret,
    )(x2[None], w2[None], scale[None, None].astype(jnp.float32),
      shift[None, None].astype(jnp.float32))
    return y[0], s[0, 0], ss[0, 0]


def _mm_wgrad_kernel(x_ref, dy_ref, scale_ref, shift_ref, dw_ref, acc_scr,
                     *, prologue: bool, act: str, nm: int):
    i = pl.program_id(1)
    xb = x_ref[0]
    if prologue:
        xb = xb * scale_ref[0].astype(xb.dtype) + \
            shift_ref[0].astype(xb.dtype)
        if act == "relu":
            xb = jnp.maximum(xb, 0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += lax.dot_general(xb, dy_ref[0], (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(i == nm - 1)
    def _fin():
        dw_ref[0] = acc_scr[...]


def _mm_wgrad(x2, dy2, scale, shift, prologue: bool, act: str,
              block_m: int, interpret: bool):
    m, cin = x2.shape
    cout = dy2.shape[1]
    block_m = min(block_m, m)
    nm = m // block_m
    if scale is None:
        scale = jnp.zeros((cin,), jnp.float32)
        shift = jnp.zeros((cin,), jnp.float32)
    kern = functools.partial(_mm_wgrad_kernel, prologue=prologue, act=act,
                             nm=nm)
    dw = pl.pallas_call(
        kern,
        grid=(1, nm),
        in_specs=[
            pl.BlockSpec((1, block_m, cin), lambda j, i: (0, i, 0)),
            pl.BlockSpec((1, block_m, cout), lambda j, i: (0, i, 0)),
            pl.BlockSpec((1, 1, cin), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, cin), lambda j, i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, cin, cout), lambda j, i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, cin, cout), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cin, cout), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * m * cin * cout,
            bytes_accessed=(x2.size * x2.dtype.itemsize +
                            dy2.size * dy2.dtype.itemsize +
                            cin * cout * 4),
            transcendentals=0),
        interpret=interpret,
    )(x2[None], dy2[None], scale[None, None].astype(jnp.float32),
      shift[None, None].astype(jnp.float32))
    return dw[0]


# ---------------------------------------------------------------------------
# NHWC 3x3 kernels: im2col assembled in VMEM, nine MXU taps per block
# ---------------------------------------------------------------------------

def _c3_prologue(xa, scale_ref, shift_ref, prologue: bool, act: str,
                 pad: int, h_valid: int, w_valid: int):
    """In-kernel BN apply (+ReLU) masked to the pre-padding valid region:
    the zero-padded border must stay zero THROUGH the affine prologue
    (relu(0*scale+shift) != 0 in general)."""
    if not prologue:
        return xa
    a = xa * scale_ref[0].astype(xa.dtype) + shift_ref[0].astype(xa.dtype)
    if act == "relu":
        a = jnp.maximum(a, 0)
    hp, wp = xa.shape[0], xa.shape[1]
    rows = lax.broadcasted_iota(jnp.int32, (hp, wp), 0)
    cols = lax.broadcasted_iota(jnp.int32, (hp, wp), 1)
    valid = ((rows >= pad) & (rows < pad + h_valid)
             & (cols >= pad) & (cols < pad + w_valid))
    return jnp.where(valid[:, :, None], a, jnp.zeros_like(a))


def _c3_taps(a, base, stride: int, block_h: int, wo: int, c: int):
    """Yield the nine [block_h*wo, c] im2col tap tiles for output-row
    block starting at input row ``base`` (VMEM-resident; never in HBM)."""
    rows_in = (block_h - 1) * stride + 1
    cols_in = (wo - 1) * stride + 1
    for t in range(9):
        dh, dw = divmod(t, 3)
        sub = lax.dynamic_slice(a, (base + dh, dw, 0), (rows_in, cols_in, c))
        yield t, sub[::stride, ::stride, :].reshape(block_h * wo, c)


def _c3_kernel(x_ref, w_ref, scale_ref, shift_ref, y_ref, s_ref, ss_ref,
               s_scr, ss_scr, *, prologue: bool, act: str, stats: bool,
               stride: int, block_h: int, wo: int, pad: int, h_valid: int,
               w_valid: int, n_total: int, nh: int):
    n = pl.program_id(0)
    i = pl.program_id(1)
    c = x_ref.shape[3]
    k = y_ref.shape[3]
    a = _c3_prologue(x_ref[0], scale_ref, shift_ref, prologue, act, pad,
                     h_valid, w_valid)
    acc = jnp.zeros((block_h * wo, k), jnp.float32)
    for t, tap in _c3_taps(a, i * block_h * stride, stride, block_h, wo, c):
        acc = acc + lax.dot_general(tap, w_ref[t], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    y_ref[0] = acc.reshape(block_h, wo, k).astype(y_ref.dtype)
    if stats:
        @pl.when((n == 0) & (i == 0))
        def _init():
            s_scr[...] = jnp.zeros_like(s_scr)
            ss_scr[...] = jnp.zeros_like(ss_scr)

        s_scr[...] += jnp.sum(acc, axis=0, keepdims=True)
        ss_scr[...] += jnp.sum(acc * acc, axis=0, keepdims=True)

        @pl.when((n == n_total - 1) & (i == nh - 1))
        def _fin():
            s_ref[...] = s_scr[...]
            ss_ref[...] = ss_scr[...]
    else:
        @pl.when((n == n_total - 1) & (i == nh - 1))
        def _fin0():
            s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)
            ss_ref[...] = jnp.zeros(ss_ref.shape, ss_ref.dtype)


def _c3(xp, wt, scale, shift, prologue: bool, act: str, stats: bool,
        stride: int, block_h: int, h_valid: int, w_valid: int,
        interpret: bool):
    """xp: [N, Hp, Wp, C] pre-padded input; wt: [9, C, K] tap matrices.
    Returns (y [N, Ho, Wo, K], s [K] f32, ss [K] f32)."""
    n, hp, wp, c = xp.shape
    k = wt.shape[2]
    ho = (hp - 3) // stride + 1
    wo = (wp - 3) // stride + 1
    block_h = min(block_h, ho)
    nh = ho // block_h
    if scale is None:
        scale = jnp.zeros((c,), jnp.float32)
        shift = jnp.zeros((c,), jnp.float32)
    kern = functools.partial(
        _c3_kernel, prologue=prologue, act=act, stats=stats, stride=stride,
        block_h=block_h, wo=wo, pad=1, h_valid=h_valid, w_valid=w_valid,
        n_total=n, nh=nh)
    y, s, ss = pl.pallas_call(
        kern,
        grid=(n, nh),
        in_specs=[
            # whole padded image per batch index: Pallas re-DMAs only when
            # the block index changes, so the image loads once per n
            pl.BlockSpec((1, hp, wp, c), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((9, c, k), lambda b, i: (0, 0, 0)),
            pl.BlockSpec((1, c), lambda b, i: (0, 0)),
            pl.BlockSpec((1, c), lambda b, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_h, wo, k), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, k), lambda b, i: (0, 0)),
            pl.BlockSpec((1, k), lambda b, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, k), xp.dtype),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * 9 * n * ho * wo * c * k,
            bytes_accessed=(xp.size * xp.dtype.itemsize +
                            n * ho * wo * k * xp.dtype.itemsize +
                            wt.size * wt.dtype.itemsize),
            transcendentals=0),
        interpret=interpret,
    )(xp, wt, scale[None].astype(jnp.float32),
      shift[None].astype(jnp.float32))
    return y, s[0], ss[0]


def _c3_wgrad_kernel(x_ref, dy_ref, scale_ref, shift_ref, dw_ref, acc_scr,
                     *, prologue: bool, act: str, stride: int, block_h: int,
                     wo: int, pad: int, h_valid: int, w_valid: int,
                     n_total: int, nh: int):
    n = pl.program_id(0)
    i = pl.program_id(1)
    c = x_ref.shape[3]
    k = dy_ref.shape[3]
    a = _c3_prologue(x_ref[0], scale_ref, shift_ref, prologue, act, pad,
                     h_valid, w_valid)
    dyb = dy_ref[0].reshape(block_h * wo, k)

    @pl.when((n == 0) & (i == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for t, tap in _c3_taps(a, i * block_h * stride, stride, block_h, wo, c):
        acc_scr[t] += lax.dot_general(tap, dyb, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when((n == n_total - 1) & (i == nh - 1))
    def _fin():
        dw_ref[...] = acc_scr[...]


def _c3_wgrad(xp, dy, scale, shift, prologue: bool, act: str, stride: int,
              block_h: int, h_valid: int, w_valid: int, interpret: bool):
    """Returns dw tap matrices [9, C, K] f32 accumulated across the grid."""
    n, hp, wp, c = xp.shape
    k = dy.shape[3]
    ho, wo = dy.shape[1], dy.shape[2]
    block_h = min(block_h, ho)
    nh = ho // block_h
    if scale is None:
        scale = jnp.zeros((c,), jnp.float32)
        shift = jnp.zeros((c,), jnp.float32)
    kern = functools.partial(
        _c3_wgrad_kernel, prologue=prologue, act=act, stride=stride,
        block_h=block_h, wo=wo, pad=1, h_valid=h_valid, w_valid=w_valid,
        n_total=n, nh=nh)
    dw = pl.pallas_call(
        kern,
        grid=(n, nh),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_h, wo, k), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, c), lambda b, i: (0, 0)),
            pl.BlockSpec((1, c), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((9, c, k), lambda b, i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((9, c, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((9, c, k), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * 9 * n * ho * wo * c * k,
            bytes_accessed=(xp.size * xp.dtype.itemsize +
                            dy.size * dy.dtype.itemsize + 9 * c * k * 4),
            transcendentals=0),
        interpret=interpret,
    )(xp, dy, scale[None].astype(jnp.float32),
      shift[None].astype(jnp.float32))
    return dw


# ---------------------------------------------------------------------------
# Host-side entries (raw, non-differentiable; the fused_conv_bn units and
# the conv2d custom_vjp below drive autodiff through dgrad/wgrad)
# ---------------------------------------------------------------------------

def _fwd_taps(w, dtype):
    """OIHW [K, C, 3, 3] -> tap matrices [9, C, K]."""
    return jnp.transpose(w, (2, 3, 1, 0)).reshape(9, w.shape[1],
                                                  w.shape[0]).astype(dtype)


def conv2d_fwd(x, w, scale=None, shift=None, act: str = "none",
               stride: Tuple[int, int] = (1, 1),
               padding: Tuple[int, int] = (0, 0), stats: bool = True,
               block_m: Optional[int] = None, block_h: Optional[int] = None,
               interpret: Optional[bool] = None):
    """Fused conv forward: ``conv(act(x*scale+shift), w)`` plus the
    per-channel (sum, sumsq) of the output, one HBM pass.

    x: [N, H, W, C] NHWC; w: OIHW [K, C, kh, kw] with kh == kw in {1, 3}
    (1x1 requires padding (0, 0), 3x3 requires padding (1, 1)).
    scale/shift: optional [C] f32 prologue (None = no prologue);
    act: 'none' | 'relu' (prologue activation, ignored without prologue).
    Returns (y [N, Ho, Wo, K], s [K] f32, ss [K] f32); s/ss are zeros
    when ``stats=False``.
    """
    interpret = _interpret_default() if interpret is None else interpret
    prologue = scale is not None
    k = w.shape[2]
    if k == 1:
        xs = x if stride == (1, 1) else x[:, ::stride[0], ::stride[1], :]
        n, h, ww, c = xs.shape
        m = n * h * ww
        bm = block_m or _pick_block_m(m, c, w.shape[0], x.dtype)
        _enforce_mm_spec(m, c, w.shape[0], bm, x.dtype, wgrad=False)
        w2 = w.reshape(w.shape[0], c).T.astype(x.dtype)
        y2, s, ss = _mm(xs.reshape(m, c), w2, scale, shift, prologue, act,
                        stats, bm, interpret)
        return y2.reshape(n, h, ww, w.shape[0]), s, ss
    n, h, ww, c = x.shape
    s_ = stride[0]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ho = (h + 2 - 3) // s_ + 1
    bh = block_h or _pick_block_h(ho, n, h, ww, c, w.shape[0], s_, x.dtype)
    _enforce_c3_spec(n, h, ww, c, w.shape[0], bh, s_, x.dtype, wgrad=False)
    return _c3(xp, _fwd_taps(w, x.dtype), scale, shift, prologue, act,
               stats, s_, bh, h, ww, interpret)


def conv2d_dgrad(dy, w, x_shape, stride: Tuple[int, int] = (1, 1),
                 padding: Tuple[int, int] = (0, 0),
                 block_m: Optional[int] = None,
                 block_h: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Input gradient: the transposed conv run through the SAME kernels
    (1x1: matmul with w^T; 3x3: forward kernel on 180-degree-rotated taps,
    stride 2 via an outside dilation of dy)."""
    interpret = _interpret_default() if interpret is None else interpret
    k = w.shape[2]
    s_ = stride[0]
    if k == 1:
        n, ho, wo, kk = dy.shape
        m = n * ho * wo
        c = w.shape[1]
        bm = block_m or _pick_block_m(m, kk, c, dy.dtype)
        _enforce_mm_spec(m, kk, c, bm, dy.dtype, wgrad=False)
        w2t = w.reshape(kk, c).astype(dy.dtype)
        da2, _, _ = _mm(dy.reshape(m, kk), w2t, None, None, False, "none",
                        False, bm, interpret)
        da = da2.reshape(n, ho, wo, c)
        if s_ != 1:
            da = jnp.zeros(x_shape, dy.dtype).at[
                :, ::s_, ::s_, :].set(da)
        return da
    n, ho, wo, kk = dy.shape
    c = w.shape[1]
    h, ww = x_shape[1], x_shape[2]
    if s_ != 1:
        dyd = jnp.zeros((n, (ho - 1) * s_ + 1, (wo - 1) * s_ + 1, kk),
                        dy.dtype).at[:, ::s_, ::s_, :].set(dy)
    else:
        dyd = dy
    # padded length must be H + 2 so the stride-1 valid conv emits H rows
    pr_h = h + 1 - dyd.shape[1]
    pr_w = ww + 1 - dyd.shape[2]
    dyp = jnp.pad(dyd, ((0, 0), (1, pr_h), (1, pr_w), (0, 0)))
    wt = jnp.transpose(w[:, :, ::-1, ::-1], (2, 3, 0, 1)).reshape(
        9, kk, c).astype(dy.dtype)
    bh = block_h or _pick_block_h(h, n, h, ww, kk, c, 1, dy.dtype)
    _enforce_c3_spec(n, h, ww, kk, c, bh, 1, dy.dtype, wgrad=False)
    dx, _, _ = _c3(dyp, wt, None, None, False, "none", False, 1, bh, h, ww,
                   interpret)
    return dx


def conv2d_wgrad(x, dy, w_shape, scale=None, shift=None, act: str = "none",
                 stride: Tuple[int, int] = (1, 1),
                 padding: Tuple[int, int] = (0, 0),
                 block_m: Optional[int] = None,
                 block_h: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Weight gradient ``a^T @ dy`` per tap, a = act(x*scale+shift)
    recomputed in-kernel from the raw input (prologue remat — the unit
    saves only the pre-BN tensor). Returns dw in OIHW, f32."""
    interpret = _interpret_default() if interpret is None else interpret
    prologue = scale is not None
    k = w_shape[2]
    s_ = stride[0]
    if k == 1:
        xs = x if stride == (1, 1) else x[:, ::s_, ::s_, :]
        n, h, ww, c = xs.shape
        m = n * h * ww
        kk = w_shape[0]
        bm = block_m or _pick_block_m(m, c, kk, x.dtype)
        _enforce_mm_spec(m, c, kk, bm, x.dtype, wgrad=True)
        dw2 = _mm_wgrad(xs.reshape(m, c), dy.reshape(m, kk), scale, shift,
                        prologue, act, bm, interpret)
        return dw2.T.reshape(w_shape)
    n, h, ww, c = x.shape
    kk = w_shape[0]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ho = (h + 2 - 3) // s_ + 1
    bh = block_h or _pick_block_h(ho, n, h, ww, c, kk, s_, x.dtype)
    _enforce_c3_spec(n, h, ww, c, kk, bh, s_, x.dtype, wgrad=True)
    dw9 = _c3_wgrad(xp, dy, scale, shift, prologue, act, s_, bh, h, ww,
                    interpret)
    return jnp.transpose(dw9.reshape(3, 3, c, kk), (3, 2, 0, 1))


# ---------------------------------------------------------------------------
# Differentiable wrapper: the dgrad/wgrad pair wired through custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d(x, w, stride: Tuple[int, int] = (1, 1),
           padding: Tuple[int, int] = (0, 0)):
    """Differentiable Pallas conv (no prologue): the parity target vs
    ``lax.conv_general_dilated`` autodiff — values, dx, dw."""
    y, _, _ = conv2d_fwd(x, w, stride=stride, padding=padding, stats=False)
    return y


def _conv2d_vjp_fwd(x, w, stride, padding):
    return conv2d(x, w, stride, padding), (x, w)


def _conv2d_vjp_bwd(stride, padding, res, dy):
    x, w = res
    dx = conv2d_dgrad(dy, w, x.shape, stride, padding).astype(x.dtype)
    dw = conv2d_wgrad(x, dy, w.shape, stride=stride,
                      padding=padding).astype(w.dtype)
    return dx, dw


conv2d.defvjp(_conv2d_vjp_fwd, _conv2d_vjp_bwd)


# ---------------------------------------------------------------------------
# Routability + static TPU-constraint enforcement
# ---------------------------------------------------------------------------

def _specs(x_shape, w_shape, stride, dtype, block_m=None, block_h=None):
    from ...analysis.pallas_check import (spec_for_conv_matmul,
                                          spec_for_conv3x3)
    n, h, ww, c = x_shape
    kk, _, kh, _ = w_shape
    s_ = stride[0]
    if kh == 1:
        m = n * ((h + s_ - 1) // s_) * ((ww + s_ - 1) // s_)
        bm = block_m or _pick_block_m(m, c, kk, dtype)
        return [spec_for_conv_matmul(m, c, kk, bm, dtype=dtype),
                spec_for_conv_matmul(m, c, kk, bm, dtype=dtype, wgrad=True)]
    ho = (h + 2 - 3) // s_ + 1
    bh = block_h or _pick_block_h(ho, n, h, ww, c, kk, s_, dtype)
    bh_dg = block_h or _pick_block_h(h, n, h, ww, kk, c, 1, dtype)
    return [spec_for_conv3x3(n, h, ww, c, kk, bh, s_, dtype=dtype),
            spec_for_conv3x3(n, h, ww, c, kk, bh, s_, dtype=dtype,
                             wgrad=True),
            # dgrad runs the fwd kernel at stride 1 with C/K swapped
            spec_for_conv3x3(n, h, ww, kk, c, bh_dg, 1, dtype=dtype)]


def _enforce_mm_spec(m, cin, cout, bm, dtype, wgrad: bool):
    from ...analysis.pallas_check import spec_for_conv_matmul
    _enforce(spec_for_conv_matmul(m, cin, cout, bm, dtype=dtype,
                                  wgrad=wgrad), "ops/_pallas/conv.py")


def _enforce_c3_spec(n, h, w, c, k, bh, stride, dtype, wgrad: bool):
    from ...analysis.pallas_check import spec_for_conv3x3
    _enforce(spec_for_conv3x3(n, h, w, c, k, bh, stride, dtype=dtype,
                              wgrad=wgrad), "ops/_pallas/conv.py")


def supports(x_shape, w_shape, stride=(1, 1), padding=(0, 0),
             dilation=(1, 1), groups: int = 1, dtype=jnp.float32) -> bool:
    """Arithmetic routability check: shape family AND the declared block
    configuration fits the TPU constraints (over-VMEM / non-dividing
    configs take the lax path instead of failing in Mosaic). On a TPU the
    3x3 family is not routable at all — Mosaic refuses its kernels — and
    says so once per shape (P005), never in silence."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if groups != 1 or tuple(dilation) != (1, 1):
        return False
    kk, cin_w, kh, kw = w_shape
    if kh != kw or kh not in (1, 3):
        return False
    if x_shape[3] != cin_w:
        return False
    s = tuple(stride)
    if s not in ((1, 1), (2, 2)):
        return False
    if kh == 1 and tuple(padding) != (0, 0):
        return False
    if kh == 3:
        if tuple(padding) != (1, 1):
            return False
        if (x_shape[1] + 2 - 3) // s[0] + 1 < 1:
            return False
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return False
    from ...analysis.pallas_check import check_kernel_spec, report_fallback
    if kh == 3 and not _interpret_default():
        report_fallback("pallas_conv3x3",
                        f"x{tuple(x_shape)} w{tuple(w_shape)} stride{s}",
                        C3_MOSAIC_REFUSAL)
        return False
    for spec in _specs(x_shape, w_shape, s, dtype):
        if any(d.severity == "error" for d in check_kernel_spec(spec)):
            return False
    return True
