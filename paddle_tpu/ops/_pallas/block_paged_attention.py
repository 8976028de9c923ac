"""Block paged attention: the attention of a diffusion pass (several
queries a row) or of a decode step (one), read straight out of ONE page pool
of fused rows whose pages are heads first.

One ``pallas_call`` a layer. ``tables [B, M]``, ``lengths [B]`` and the layer
index are scalar-prefetch operands, the pool ``[L, NB, 2 * KH, bs, D]`` stays
in HBM, and for each row the kernel walks the row's block table and fetches
``pages_per_step`` pages a step by explicit DMA into one of two VMEM slots,
up to the row's own ``lengths[b]`` (its context and the block in flight,
which the pass has just written) and not a page further. The next step's
pages (of the same row or of the next row that has any) are in flight while
this step's are attended. Online softmax in float32; rows with ``lengths[b]
== 0`` return 0.

A page is ``[2 * KH, bs, D]``, the keys of kv head ``g`` at ``[g]`` and its
values at ``[KH + g]`` (``ops/paged_layout.py``, the fused row): 32 KB in one
stretch at SDAR's shapes, so keys AND values of a page are ONE descriptor,
which lands in its slot as it lies in the pool (``[pages, 2 * KH, bs, D]``: a
linear copy). What a call costs beside its products is the descriptors it
issues, about 28 ns each on the scalar core, serial with the products (PERF.md
section 5 has the split of a call): a full step starts and waits for its
pages unrolled with no predicate a page, a row's last step in a loop of just
its live pages.

A kv head's query rows are the ``Lq`` positions times the ``H / KH`` query
heads that share it. The step's products take one of two forms, picked from
that count (:func:`one_query`); the walk above, and the last step's clearing
of the values past the length, are the same for both:

- **per head**, several query rows a kv head (4 x 8 = 32 at SDAR's shapes).
  One kv head's ``bs`` keys of a page are one contiguous ``(bs, D)`` tile, so
  the head's keys of a step, ``slot[:, g]``, are a plain matrix ``[T, D]``
  (``T = pages_per_step * bs``; whole tiles stacked, no relayout), and the
  head's query rows multiply just them: ``[32, D] x [T, D]^T``, no masked-out
  products, a softmax state a kv head. Named ``block_paged_attention``.
- **heads joint**, ONE query row a kv head (Olmo-Hybrid's full layer at
  decode: one position, 30 query heads over 30 kv heads). A head at a time
  would be ``2 * KH`` serial one-row products and ``KH`` one-sublane softmax
  states a step. Instead the step's keys of every head, ``slot[:, :KH]``,
  are read as ONE matrix ``[pages * KH * bs, D]`` (whole tiles again):
  column ``c`` is page ``c // (KH * bs)``, kv head ``(c // bs) % KH``, token
  ``(c // (KH * bs)) * bs + c % bs`` of the step. The row's ``[KH, D]``
  queries multiply all of it in one product, the scores whose column is
  another head's are masked like those past the length, ONE softmax state
  ``[KH, 1]`` / ``[KH, D]`` is updated, and ``P x V`` against the values
  read the same way sums over each query's own head (a masked probability is
  an exact 0). The MXU does ``KH`` times the needed products, which it has
  to spare: ``paged_attention.py``'s method on the heads-first page. Named
  ``block_paged_attention_one_query``; its pages a step fill
  ``ONE_QUERY_SLOT_BYTES``.

All queries of a row see the same keys (within the block nothing is masked),
so beside the head the only mask is the row's length in its last step.
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

__all__ = ["block_paged_attention_pallas", "supported_shapes", "pages_for",
           "one_query", "SLOT_BYTES", "ONE_QUERY_SLOT_BYTES"]

# Bytes of pages fetched and attended a step (one of the two VMEM slots). At
# SDAR's page (8 heads x 16 tokens x 128 x bf16 = 32 KB) that is thirty-two
# pages, 512 tokens, and four [32, 512] float32 score tiles. Measured on the
# chip at that cell's shapes (PERF.md section 6): 16 pages 0.66 ms a
# call, 32 0.48, 48 0.52, 64 0.51; what a step costs beside its keys (the
# softmax state's update, the loop) is paid half as often at 32 as at 16, and
# past 32 a row's last step attends more masked keys than that saves.
SLOT_BYTES = 2 ** 20

# The same for the heads-joint form (:func:`one_query`). At Olmo-Hybrid's
# page (60 heads: 240 KB) that is eight pages, 128 tokens, and one [30,
# 3840] float32 score block. Measured on the chip at that cell's shapes (256
# rows, 200 of them live at a mean of 799 keys, 3.0 ms of bytes at 819 GB/s;
# PERF.md section 6): 2 pages 4.01 ms a call, 4 3.363, 8 3.357, 16 3.381; a
# step's own cost is paid once for all heads, so past 4 pages little is
# left to save, and past 8 a row's last step attends more masked keys.
ONE_QUERY_SLOT_BYTES = 2 ** 21


def one_query(qrows: int) -> bool:
    """Whether a kv head has ONE query row (``Lq * H / KH == 1``: one
    position, as many query heads as kv heads): a step's products then take
    every head at once (the heads-joint form), else a kv head at a time (the
    per-head form)."""
    return qrows == 1


def pages_for(page_bytes: int, qrows: int) -> int:
    """Pages a step: as many as fill a slot, at least one; a slot of
    ``ONE_QUERY_SLOT_BYTES`` for the heads-joint form (8 at 240 KB), else of
    ``SLOT_BYTES`` (32 at 32 KB, 4 at 240 KB)."""
    slot = ONE_QUERY_SLOT_BYTES if one_query(qrows) else SLOT_BYTES
    return max(1, slot // int(page_bytes))


_NEG = -1e30        # masked score: exp(_NEG - m) is an exact 0 for finite m
_FAR = 2 ** 30      # a token index past any row's length


def supported_shapes(q_dtype, kv_pool) -> bool:
    """Shapes the compiled kernel takes on a TPU: bf16 queries and pool
    (``[..., NB, 2 * KH, bs, D]``: an even number of heads a fused row),
    ``head_dim`` 128 (one lane tile) and ``block_size`` a multiple of the
    bf16 sublane tile (16), so that a head's keys of a page are whole
    tiles."""
    heads, bs, d = kv_pool.shape[-3:]
    return (q_dtype == jnp.bfloat16 and kv_pool.dtype == jnp.bfloat16
            and d == 128 and bs % 16 == 0 and heads % 2 == 0)


def _head(buf, slot, h):
    """Head ``h`` of a slot's fused rows as one matrix ``[T, D]``."""
    pages, _, bs, d = buf.shape[1:]
    return buf[slot, :, h].reshape(pages * bs, d)


def _softmax_step(state, s, values, dtype):
    """One online-softmax update of ``(m, l, acc)`` by the scores ``s``;
    ``values()`` is read after the probabilities, which are taken to
    ``dtype`` for the product."""
    m, l, acc = state
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jnp.dot(p.astype(dtype), values(),
                                preferred_element_type=jnp.float32)
    return m_new, l, acc


def _out(state, dtype):
    # a row without keys never entered the loop: acc 0 over l 0 -> 0
    _, l, acc = state
    return (acc / jnp.where(l > 0, l, 1.0)).astype(dtype)


def _zero(rows: int, d: int):
    return (jnp.full((rows, 1), _NEG, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, d), jnp.float32))


def _per_head(q_ref, o_ref, buf, *, scale: float, kh: int, qrows: int):
    """A kv head at a time: head ``g``'s ``qrows`` query rows against its
    keys of the step, ``[qrows, D] x [T, D]^T``, a softmax state a head.
    Returns ``(begin, update, end)`` of a row."""
    pages, _, bs, d = buf.shape[1:]
    tok = lax.broadcasted_iota(jnp.int32, (qrows, pages * bs), 1)

    def begin(b):
        qs = [q_ref[b, pl.ds(g * qrows, qrows), :] for g in range(kh)]
        return qs, tuple(_zero(qrows, d) for _ in range(kh))

    def update(qs, state, slot, left):
        new = []
        for g in range(kh):
            s = lax.dot_general(qs[g], _head(buf, slot, g),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(tok < left, s * scale, _NEG)
            new.append(_softmax_step(
                state[g], s, lambda g=g: _head(buf, slot, kh + g),
                buf.dtype))
        return tuple(new)

    def end(b, state):
        for g in range(kh):
            o_ref[b, pl.ds(g * qrows, qrows), :] = _out(state[g],
                                                        o_ref.dtype)

    return begin, update, end


def _heads_joint(q_ref, o_ref, buf, *, scale: float, kh: int):
    """Every head at once, one query row a kv head: the step's keys of all
    heads read as ONE matrix ``[pages * KH * bs, D]`` (``buf[slot, :,
    :KH]``, whole ``(bs, D)`` tiles stacked), the row's ``[KH, D]`` queries
    against all of it in one product, the columns of another head masked
    out, ONE softmax state ``[KH, 1]`` / ``[KH, D]``, and one ``P x V``
    against the values read the same way (a masked column's probability is
    an exact 0, so each query sums over its own head). Returns ``(begin,
    update, end)`` of a row."""
    pages, _, bs, d = buf.shape[1:]
    cols = pages * kh * bs
    col = lax.broadcasted_iota(jnp.int32, (kh, cols), 1)
    # column c: page c // (KH bs), kv head (c // bs) % KH, token of the step
    # (c // (KH bs)) bs + c % bs; a column of another head than the row's
    # stands past any length, so one comparison with the length masks both
    key_tok = jnp.where(
        lax.broadcasted_iota(jnp.int32, (kh, cols), 0) == col // bs % kh,
        col // (kh * bs) * bs + col % bs, _FAR)

    def every_head(slot, lo):
        return buf[slot, :, lo:lo + kh].reshape(cols, d)

    def begin(b):
        return q_ref[b], _zero(kh, d)

    def update(q, state, slot, left):
        s = lax.dot_general(q, every_head(slot, 0), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(key_tok < left, s * scale, _NEG)
        return _softmax_step(state, s, lambda: every_head(slot, kh),
                             buf.dtype)

    def end(b, state):
        o_ref[b] = _out(state, o_ref.dtype)

    return begin, update, end


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, kv_hbm, o_ref, buf,
            sems, *, scale: float, kh: int, qrows: int):
    nrows = tables_ref.shape[0]
    pages, _, bs, d = buf.shape[1:]
    t_step = pages * bs                 # tokens a step
    layer = layer_ref[0]

    def each_page(b, i, do):
        """``do(j)`` for every page ``j`` of step ``i`` of row ``b`` under
        the row's length: unrolled and without a predicate a page in a full
        step, a loop of just the live pages in a row's last."""
        left = lengths_ref[b] - i * t_step
        n = jnp.clip((left + bs - 1) // bs, 0, pages)

        @pl.when(n == pages)
        def _():
            for j in range(pages):
                do(j)

        @pl.when(n < pages)
        def _():
            def one(j, carry):
                do(j)
                return carry
            lax.fori_loop(0, n, one, 0)

    def start(b, i, slot):
        """Fetch step ``i`` of row ``b`` into ``slot``: one descriptor a page
        under the row's length, none past it."""
        each_page(b, i, lambda j: pltpu.make_async_copy(
            kv_hbm.at[layer, tables_ref[b, i * pages + j]], buf.at[slot, j],
            sems.at[slot]).start())

    def wait(b, i, slot):
        """Wait for what :func:`start` fetched: the slot's semaphore counts
        bytes, and each wait takes one page's off it, whichever page."""
        each_page(b, i, lambda j: pltpu.make_async_copy(
            kv_hbm.at[layer, 0], buf.at[slot, 0], sems.at[slot]).wait())

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

    if one_query(qrows):
        begin, update, end = _heads_joint(q_ref, o_ref, buf, scale=scale,
                                          kh=kh)
    else:
        begin, update, end = _per_head(q_ref, o_ref, buf, scale=scale,
                                       kh=kh, qrows=qrows)
    tok_of_row = lax.broadcasted_iota(jnp.int32, (t_step, 1), 0)

    def row_body(b, slot):
        length = lengths_ref[b]
        steps = (length + t_step - 1) // t_step
        q, zero = begin(b)

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
                # so V is cleared there (scores are masked)
                for g in range(kh):
                    v = _head(buf, slot, kh + g)
                    buf[slot, :, kh + g] = jnp.where(
                        tok_of_row < left, v,
                        jnp.zeros_like(v)).reshape(pages, bs, d)

            return update(q, state, slot, left), 1 - slot

        state, slot = lax.fori_loop(0, steps, step_body, (zero, slot))
        end(b, state)
        return slot

    lax.fori_loop(0, nrows, row_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "interpret"))
def _block_call(q, kv_pool, tables, lengths, layer, *, scale,
                pages_per_step, interpret):
    b, rows, d = q.shape                # rows = KH * (Lq * H / KH)
    heads, bs = kv_pool.shape[-3:-1]
    kh = heads // 2
    qrows = rows // kh
    kernel = functools.partial(_kernel, scale=scale, kh=kh, qrows=qrows)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_step, heads, bs, d), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the queries and outputs of every row are resident (2 x 4 MiB
            # at 128 rows x 128 query rows x 128 bf16)
            vmem_limit_bytes=48 * 2 ** 20),
        name=("block_paged_attention_one_query" if one_query(qrows)
              else "block_paged_attention"),
        interpret=interpret,
    )(layer, tables, lengths, q, kv_pool)


def block_paged_attention_pallas(q, kv_pool, tables, lengths, *, layer=0,
                                 scale: Optional[float] = None,
                                 pages_per_step: Optional[int] = None,
                                 interpret: bool = False):
    """``q [B, Lq, H, D]`` over the pages ``tables [B, M]`` names in
    ``kv_pool`` (``[L, NB, 2 * KH, bs, D]``, or one layer's ``[NB, 2 * KH,
    bs, D]``: keys the first ``KH`` heads of a page, values the rest), every
    query of row ``b`` over the row's first ``lengths[b]`` keys; returns
    ``[B, Lq, H, D]``. ``layer`` may be a traced scalar: the unrolled layers
    of a program then share one traced and lowered kernel. ``pages_per_step``
    None: from a page's bytes (:func:`pages_for`)."""
    b, lq, h, d = q.shape
    if kv_pool.ndim == 4:
        kv_pool, layer = kv_pool[None], 0
    heads = kv_pool.shape[-3]
    if heads % 2:
        raise ValueError(f"a fused row holds keys and values: {heads} heads "
                         "a page is not an even number")
    kh = heads // 2
    if h % kh:
        raise ValueError(f"query heads ({h}) not a multiple of kv heads "
                         f"({kh})")
    g = h // kh
    # a kv head's query rows together: [B, KH, Lq * G, D]
    qr = q.reshape(b, lq, kh, g, d).transpose(0, 2, 1, 3, 4)
    if pages_per_step is None:
        pages_per_step = pages_for(math.prod(kv_pool.shape[-3:])
                                   * kv_pool.dtype.itemsize, lq * g)
    pages = max(1, min(pages_per_step, tables.shape[1]))
    out = _block_call(
        qr.reshape(b, kh * lq * g, d), kv_pool, tables.astype(jnp.int32),
        lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        scale=float(scale if scale is not None else 1.0 / math.sqrt(d)),
        pages_per_step=pages, interpret=interpret)
    return out.reshape(b, kh, lq, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, lq, h, d)
