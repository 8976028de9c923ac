"""Block paged attention: the attention of a diffusion pass, several queries a
row, read straight out of a page pool whose pages are heads first.

One ``pallas_call`` a layer. ``tables [B, M]``, ``lengths [B]`` and the layer
index are scalar-prefetch operands, the pool ``[L, NB, KH, bs, D]`` stays in
HBM, and for each row the kernel walks the row's block table and fetches
``pages_per_step`` K pages and as many V pages a step by explicit DMA into one
of two VMEM slots, up to the row's own ``lengths[b]`` (its context and the
block in flight, which the pass has just written) and not a page further. The
next step's pages (of the same row or of the next row that has any) are in
flight while this step's are attended. Online softmax in float32; rows with
``lengths[b] == 0`` return 0.

A page is ``[KH, bs, D]``: one kv head's ``bs`` keys are one contiguous
``(bs, D)`` tile, and a step's pages land in VMEM as ``[KH, T, D]`` (``T =
pages_per_step * bs``; the DMA puts page ``j`` at rows ``j * bs`` of every
head). So each kv head's keys of the step are a plain matrix ``[T, D]``, and
the head's query rows (the ``Lq`` positions of the block times the ``H / KH``
query heads that share it, 4 x 8 = 32 at SDAR's shapes) multiply just them:
``[32, D] x [T, D]^T``, no masked-out products and no relayout. All queries of
a row see the same keys (within the block nothing is masked), so the only mask
is the row's length in its last step.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_paged_attention_pallas", "supported_shapes",
           "PAGES_PER_STEP"]

# Pages of K (and of V) fetched and attended a step. At the serving cell's
# page (4 heads x 16 tokens x 128 x bf16 = 16 KB) sixteen pages are 256
# tokens: 2 x 2 x 256 KB of VMEM slots and four [32, 256] float32 score tiles.
PAGES_PER_STEP = 16

_NEG = -1e30        # masked score: exp(_NEG - m) is an exact 0 for finite m


def supported_shapes(q_dtype, k_pool) -> bool:
    """Shapes the compiled kernel takes on a TPU: bf16 queries and pool
    (``[..., NB, KH, bs, D]``), ``head_dim`` 128 (one lane tile) and
    ``block_size`` a multiple of the bf16 sublane tile (16), so that a head's
    keys of a page are whole tiles."""
    bs, d = k_pool.shape[-2:]
    return (q_dtype == jnp.bfloat16 and k_pool.dtype == jnp.bfloat16
            and d == 128 and bs % 16 == 0)


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *, scale: float, pages: int, bs: int, kh: int,
            qrows: int):
    nrows, m_pages = tables_ref.shape
    t_step = pages * bs                 # tokens a step
    layer = layer_ref[0]

    def copies(b, i, slot):
        """The DMAs of step ``i`` of row ``b`` into ``slot``: each page of K
        and V under the row's length, none past it."""
        out = []
        for j in range(pages):
            p = i * pages + j
            live = p * bs < lengths_ref[b]
            page = tables_ref[b, jnp.minimum(p, m_pages - 1)]
            dst = pl.ds(j * bs, bs)
            out.append((live, pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, :, dst, :],
                sems.at[0, slot])))
            out.append((live, pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, :, dst, :],
                sems.at[1, slot])))
        return out

    def start(b, i, slot):
        for live, cp in copies(b, i, slot):
            pl.when(live)(cp.start)

    def wait(b, i, slot):
        for live, cp in copies(b, i, slot):
            pl.when(live)(cp.wait)

    def next_row(b):
        """The first row after ``b`` with any key (``nrows`` if none)."""
        return lax.while_loop(
            lambda r: jnp.logical_and(
                r < nrows, lengths_ref[jnp.minimum(r, nrows - 1)] == 0),
            lambda r: r + 1, b + 1)

    first = next_row(-1)

    @pl.when(first < nrows)
    def _():
        start(first, 0, 0)

    tok = lax.broadcasted_iota(jnp.int32, (qrows, t_step), 1)
    tok_of_row = lax.broadcasted_iota(jnp.int32, (t_step, 1), 0)
    d = q_ref.shape[-1]

    def row_body(b, slot):
        length = lengths_ref[b]
        steps = (length + t_step - 1) // t_step
        qs = [q_ref[b, pl.ds(g * qrows, qrows), :] for g in range(kh)]

        def step_body(i, carry):
            state, slot = carry
            more = i + 1 < steps
            nb = jnp.where(more, b, next_row(b))
            ni = jnp.where(more, i + 1, 0)

            @pl.when(nb < nrows)
            def _():
                start(jnp.minimum(nb, nrows - 1), ni, 1 - slot)

            wait(b, i, slot)
            left = length - i * t_step      # tokens of this step under length

            @pl.when(left < t_step)
            def _():
                # the row's last step: slots past the length (and pages that
                # were not fetched) hold whatever was there; 0 * NaN is NaN,
                # so V is cleared there (scores are masked below)
                for g in range(kh):
                    v = vbuf[slot, g]
                    vbuf[slot, g] = jnp.where(tok_of_row < left, v,
                                              jnp.zeros_like(v))

            new = []
            for g in range(kh):
                m, l, acc = state[g]
                s = lax.dot_general(qs[g], kbuf[slot, g],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                s = jnp.where(tok < left, s * scale, _NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = alpha * acc + jnp.dot(
                    p.astype(vbuf.dtype), vbuf[slot, g],
                    preferred_element_type=jnp.float32)
                new.append((m_new, l, acc))
            return tuple(new), 1 - slot

        zero = tuple((jnp.full((qrows, 1), _NEG, jnp.float32),
                      jnp.zeros((qrows, 1), jnp.float32),
                      jnp.zeros((qrows, d), jnp.float32))
                     for _ in range(kh))
        state, slot = lax.fori_loop(0, steps, step_body, (zero, slot))
        for g in range(kh):
            _, l, acc = state[g]
            # a row without keys never entered the loop: acc 0 over l 0 -> 0
            o_ref[b, pl.ds(g * qrows, qrows), :] = (
                acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, nrows, row_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "kh", "interpret"))
def _block_call(q, k_pool, v_pool, tables, lengths, layer, *, scale,
                pages_per_step, kh, interpret):
    b, rows, d = q.shape                # rows = KH * (Lq * H / KH)
    bs = k_pool.shape[-2]
    t_step = pages_per_step * bs
    kernel = functools.partial(_kernel, scale=scale, pages=pages_per_step,
                               bs=bs, kh=kh, qrows=rows // kh)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((2, kh, t_step, d), k_pool.dtype),
                            pltpu.VMEM((2, kh, t_step, d), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the queries and outputs of every row are resident (2 x 4 MiB
            # at 128 rows x 128 query rows x 128 bf16)
            vmem_limit_bytes=48 * 2 ** 20),
        name="block_paged_attention",
        interpret=interpret,
    )(layer, tables, lengths, q, k_pool, v_pool)


def block_paged_attention_pallas(q, k_pool, v_pool, tables, lengths, *,
                                 layer=0, scale: Optional[float] = None,
                                 pages_per_step: int = PAGES_PER_STEP,
                                 interpret: bool = False):
    """``q [B, Lq, H, D]`` over the pages ``tables [B, M]`` names in
    ``k_pool`` / ``v_pool`` (``[L, NB, KH, bs, D]``, or one layer's ``[NB,
    KH, bs, D]``), every query of row ``b`` over the row's first
    ``lengths[b]`` keys; returns ``[B, Lq, H, D]``. ``layer`` may be a traced
    scalar: the unrolled layers of a program then share one traced and
    lowered kernel."""
    b, lq, h, d = q.shape
    if k_pool.ndim == 4:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    kh = k_pool.shape[-3]
    if h % kh:
        raise ValueError(f"query heads ({h}) not a multiple of kv heads "
                         f"({kh})")
    g = h // kh
    # a kv head's query rows together: [B, KH, Lq * G, D]
    qr = q.reshape(b, lq, kh, g, d).transpose(0, 2, 1, 3, 4)
    pages = max(1, min(pages_per_step, tables.shape[1]))
    out = _block_call(
        qr.reshape(b, kh * lq * g, d), k_pool, v_pool,
        tables.astype(jnp.int32), lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        scale=float(scale if scale is not None else 1.0 / math.sqrt(d)),
        pages_per_step=pages, kh=kh, interpret=interpret)
    return out.reshape(b, kh, lq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, lq, h, d)
