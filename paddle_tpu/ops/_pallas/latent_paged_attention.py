"""Paged single-query attention over a latent (MLA) page pool, absorbed form:
the decode step's attention of a model whose cache holds one latent row a
token, ``[c_kv | k_rope]``, and no keys or values a head.

The absorbed query of head ``i`` is ``[q_nope_i W_UK,i^T | q_rope_i]``, as
wide as the row, so a head's score against a token is one dot product with
the token's row, every head reads the SAME row, and the head's value is the
row's first ``value_dim`` entries (``c_kv``; the caller applies ``W_UV``
afterwards). One ``pallas_call`` a layer, one grid step a row of the batch:

- ``tables [B, M]``, ``lengths [B]`` and the layer index are scalar-prefetch
  operands; the pool ``[L, NB, bs, W]`` stays in HBM; the row's queries
  ``[H, W]`` and outputs ``[H, value_dim]`` are blocks that Pallas moves while
  the neighbouring rows compute;
- the row's pages are fetched ``pages_per_step`` a step by explicit DMA into
  one of two VMEM slots, up to the row's own ``lengths[b]`` and not a page
  further; the next step's pages (of the same row, or of the next row that
  has any) are in flight while this step's are attended, across grid steps
  (the slots, their semaphores and the slot in use persist in scratch);
- a step is two MXU products, ``[H, W] x [T, W]^T -> [H, T]`` and
  ``[H, T] x [T, value_dim]``, with the online softmax in float32 between
  them. Rows with ``lengths[b] == 0`` return 0.

At 128 heads the products have 128 rows, a full MXU tile: the algorithm
needs ``2 * (576 + 512) * H`` FLOPs a key for ``2 * 576`` bytes at the
published widths, 242 FLOP a byte, the v5e's ridge; the kernel's rows are
the padded 640.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_paged_attention_pallas", "supported_shapes",
           "PAGES_PER_STEP"]

# Pages fetched and attended a step: 32 pages of 16 tokens are 512 tokens,
# 2 x 640 KB of VMEM slots at a row of 640 and a [128, 512] float32 score
# tile. At the serving cell's shape (256 rows of 600-3,500 keys, 128 heads)
# a call took 3.70 ms at 8 pages, 2.71 at 16 and 2.48 at 32 (my chip run,
# PR 30): fewer, longer steps hide more of the DMA issue.
PAGES_PER_STEP = 32

_NEG = -1e30        # masked score: exp(_NEG - m) is an exact 0 for finite m


def supported_shapes(q_dtype, pool, value_dim: int) -> bool:
    """Shapes the compiled kernel takes on a TPU: bf16 queries and pool
    (``[..., NB, bs, W]``), the row and its value part whole lane tiles
    (``W % 128 == 0``, ``value_dim % 128 == 0``: a page then moves by one
    aligned DMA; a model pads its row up, 576 -> 640) and pages that stack
    without a relayout (``block_size`` a multiple of the bf16 sublane tile,
    16)."""
    bs, w = pool.shape[-2:]
    return (q_dtype == jnp.bfloat16 and pool.dtype == jnp.bfloat16
            and value_dim % 128 == 0 and 0 < value_dim <= w
            and w % 128 == 0 and bs % 16 == 0)


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, kv_hbm, o_ref,
            kvbuf, sems, slot_ref, *, scale: float, pages: int, bs: int,
            value_dim: int):
    nrows, m_pages = tables_ref.shape
    t_step = pages * bs                 # tokens a step
    layer = layer_ref[0]
    b = pl.program_id(0)

    def copies(r, i, slot):
        """The DMAs of step ``i`` of row ``r`` into ``slot``: each page
        under the row's length, none past it."""
        out = []
        for j in range(pages):
            p = i * pages + j
            live = p * bs < lengths_ref[r]
            page = tables_ref[r, jnp.minimum(p, m_pages - 1)]
            out.append((live, pltpu.make_async_copy(
                kv_hbm.at[layer, page], kvbuf.at[slot, pl.ds(j * bs, bs)],
                sems.at[slot])))
        return out

    def start(r, i, slot):
        for live, cp in copies(r, i, slot):
            pl.when(live)(cp.start)

    def wait(r, i, slot):
        for live, cp in copies(r, i, slot):
            pl.when(live)(cp.wait)

    def next_row(r):
        """The first row after ``r`` with any key (``nrows`` if none)."""
        return lax.while_loop(
            lambda x: jnp.logical_and(
                x < nrows, lengths_ref[jnp.minimum(x, nrows - 1)] == 0),
            lambda x: x + 1, r + 1)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        first = next_row(-1)

        @pl.when(first < nrows)
        def _():
            start(first, 0, 0)

    length = lengths_ref[b]
    steps = (length + t_step - 1) // t_step
    q = q_ref[0]                                        # [H, W]
    h = q.shape[0]
    tok = lax.broadcasted_iota(jnp.int32, (h, t_step), 1)
    tok_of_row = lax.broadcasted_iota(jnp.int32, (t_step, 1), 0)

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
            # the row's last step: rows past the length (and pages that
            # were not fetched) hold whatever was there; 0 * NaN is NaN, so
            # they are cleared (their scores are masked below)
            kv = kvbuf[slot]
            kvbuf[slot] = jnp.where(tok_of_row < left, kv,
                                    jnp.zeros_like(kv))

        kv = kvbuf[slot]                                # [T, W]
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(tok < left, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :value_dim],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc, 1 - slot

    m, l, acc, slot = lax.fori_loop(
        0, steps, step_body,
        (jnp.full((h, 1), _NEG, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, value_dim), jnp.float32), slot_ref[0]))
    slot_ref[0] = slot
    # a row without keys never entered the loop: acc 0 over l 0 -> 0
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "pages_per_step", "interpret"))
def _latent_paged_call(q, pool, tables, lengths, layer, *, scale, value_dim,
                       pages_per_step, interpret):
    b, h, w = q.shape
    bs = pool.shape[-2]
    kernel = functools.partial(_kernel, scale=scale, pages=pages_per_step,
                               bs=bs, value_dim=value_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, h, w), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, value_dim),
                                   lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_step * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_paged_attention",
        interpret=interpret,
    )(layer, tables, lengths, q, pool)


def latent_paged_attention_pallas(q, pool, tables, lengths, *, value_dim: int,
                                  scale: float, layer=0,
                                  pages_per_step: int = PAGES_PER_STEP,
                                  interpret: bool = False):
    """Absorbed queries ``q [B, 1, H, W]`` over the pages ``tables [B, M]``
    names in ``pool`` (``[L, NB, bs, W]``, or one layer's ``[NB, bs, W]``),
    each row up to ``lengths[b]`` keys; returns ``[B, 1, H, value_dim]``, the
    softmax-weighted sum of the rows' first ``value_dim`` entries. ``layer``
    may be a traced scalar: the unrolled layers of a decode program then
    share one traced and lowered kernel."""
    b, sq, h, w = q.shape
    if sq != 1:
        raise ValueError(f"latent paged attention needs Sq=1, got {sq}")
    if pool.ndim == 3:
        pool, layer = pool[None], 0
    if pool.shape[-1] != w:
        raise ValueError(f"absorbed queries are {w} wide, the pool's rows "
                         f"{pool.shape[-1]}")
    pages = max(1, min(pages_per_step, tables.shape[1]))
    out = _latent_paged_call(
        q[:, 0], pool, tables.astype(jnp.int32), lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), scale=float(scale),
        value_dim=int(value_dim), pages_per_step=pages, interpret=interpret)
    return out[:, None]
