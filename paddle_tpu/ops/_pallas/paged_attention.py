"""Paged single-query attention: the serving decode step's attention, read
straight out of the page pool.

One ``pallas_call`` a layer. ``tables [B, M]``, ``lengths [B]`` and the layer
index are scalar-prefetch operands (device arrays: one executable serves every
step), the pool ``[L, NB, bs, KH, D]`` stays in HBM, and for each row the
kernel walks the row's block table and fetches ``pages_per_step`` K pages and
as many V pages a step by explicit DMA into one of two VMEM slots, up to the
row's own ``lengths[b]`` and not a page further. The next step's pages (of the
same row or of the next row that has any) are in flight while this step's are
attended. Online softmax in float32; rows with ``lengths[b] == 0`` return 0.

How scores are formed with the head axis inside a page (``[bs, KH, D]``, a
token's heads are one ``(KH, D)`` tile): a step's pages are read as the 2-D
matrix ``[T*KH, D]`` (row ``c`` is token ``c // KH``, kv head ``c % KH``) and
all ``H`` queries are multiplied against all of it on the MXU,
``[H, D] x [T*KH, D]^T -> [H, T*KH]``. Only the entries whose column's kv head
is the row's (``h // (H // KH) == c % KH``) are attention scores; the others
are masked to ``-inf`` before the softmax, so their probabilities are exact
zeros and ``P [H, T*KH] x V [T*KH, D]`` sums over just the row's own head. The
MXU does ``KH`` times the needed multiplies (idle otherwise: the kernel is
bound by the page reads), and no relayout of a page is ever made.
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

__all__ = ["paged_attention_pallas", "supported_shapes", "PAGES_PER_STEP"]

# Pages of K (and of V) fetched and attended a step. At the serving cell's
# page (16 tokens x 16 heads x 128 x bf16 = 64 KB) eight pages are 128 tokens:
# 2 x 2 x 512 KB of VMEM slots and a [16, 2048] float32 score tile.
PAGES_PER_STEP = 8

_NEG = -1e30        # masked score: exp(_NEG - m) is an exact 0 for finite m


def supported_shapes(q_dtype, k_pool) -> bool:
    """Shapes the compiled kernel takes on a TPU: bf16 queries and pool
    (``[..., NB, bs, KH, D]``), ``head_dim`` 128 (one lane tile), and pages
    whose ``[bs, KH, D]`` reads as ``[bs*KH, D]`` without a relayout:
    ``block_size`` and the kv heads both multiples of the bf16 sublane tile
    (16)."""
    bs, kh, d = k_pool.shape[-3:]
    return (q_dtype == jnp.bfloat16 and k_pool.dtype == jnp.bfloat16
            and d == 128 and bs % 16 == 0 and kh % 16 == 0)


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *, scale: float, pages: int, bs: int, kh: int,
            group: int):
    nrows, m_pages = tables_ref.shape
    rows = bs * kh                      # rows of one page read as 2-D
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
            dst = pl.ds(j * rows, rows)
            out.append((live, pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, dst], sems.at[0, slot])))
            out.append((live, pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, dst], sems.at[1, slot])))
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

    # [H, T*KH]: column c is token c // kh of the step, kv head c % kh
    shape = (q_ref.shape[1], t_step * kh)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    own_head = (lax.broadcasted_iota(jnp.int32, shape, 0) // group
                == col % kh)
    tok = col // kh
    tok_of_row = lax.broadcasted_iota(jnp.int32, (t_step * kh, 1), 0) // kh

    def row_body(b, slot):
        length = lengths_ref[b]
        steps = (length + t_step - 1) // t_step
        q = q_ref[b]

        def step_body(i, carry):
            m, l, acc, slot = carry
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
                v = vbuf[slot]
                vbuf[slot] = jnp.where(tok_of_row < left, v,
                                       jnp.zeros_like(v))

            s = lax.dot_general(q, kbuf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(jnp.logical_and(own_head, tok < left), s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(vbuf.dtype), vbuf[slot],
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - slot

        h, d = q.shape
        m, l, acc, slot = lax.fori_loop(
            0, steps, step_body,
            (jnp.full((h, 1), _NEG, jnp.float32),
             jnp.zeros((h, 1), jnp.float32),
             jnp.zeros((h, d), jnp.float32), slot))
        # a row without keys never entered the loop: acc 0 over l 0 -> 0
        o_ref[b] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, nrows, row_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "interpret"))
def _paged_call(q, k_pool, v_pool, tables, lengths, layer, *, scale,
                pages_per_step, interpret):
    b, h, d = q.shape
    n_layers, nb, bs, kh, _ = k_pool.shape
    # a page read as [bs*KH, D]: the same bytes (a token's heads are whole
    # tiles), so the reshape of the pool is a bitcast, never a copy
    k2 = k_pool.reshape(n_layers, nb, bs * kh, d)
    v2 = v_pool.reshape(n_layers, nb, bs * kh, d)
    step_rows = pages_per_step * bs * kh
    kernel = functools.partial(_kernel, scale=scale, pages=pages_per_step,
                               bs=bs, kh=kh, group=h // kh)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((2, step_rows, d), k_pool.dtype),
                            pltpu.VMEM((2, step_rows, d), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_single_query_attention",
        interpret=interpret,
    )(layer, tables, lengths, q, k2, v2)


def paged_attention_pallas(q, k_pool, v_pool, tables, lengths, *, layer=0,
                           scale: Optional[float] = None,
                           pages_per_step: int = PAGES_PER_STEP,
                           interpret: bool = False):
    """``q [B, 1, H, D]`` over the pages ``tables [B, M]`` names in
    ``k_pool`` / ``v_pool`` (``[L, NB, bs, KH, D]``, or one layer's
    ``[NB, bs, KH, D]``), each row up to ``lengths[b]`` keys; returns
    ``[B, 1, H, D]``. ``layer`` may be a traced scalar: the unrolled layers
    of a decode program then share one traced and lowered kernel."""
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged attention needs Sq=1, got {sq}")
    if k_pool.ndim == 4:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    kh = k_pool.shape[-2]
    if h % kh:
        raise ValueError(f"query heads ({h}) not a multiple of kv heads "
                         f"({kh})")
    pages = max(1, min(pages_per_step, tables.shape[1]))
    out = _paged_call(
        q[:, 0], k_pool, v_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        scale=float(scale if scale is not None else 1.0 / math.sqrt(d)),
        pages_per_step=pages, interpret=interpret)
    return out[:, None]
