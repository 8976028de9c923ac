"""The decode step of a gated-delta-rule layer over a pool of states, each row's
state read by its slot and written back in place.

One ``pallas_call`` a layer, a grid step a row. The pool ``[L, slots, d_k, H *
d_v]`` float32 stays in HBM and is the call's output too (aliased: the
program that takes it donated updates it where it lies); ``slots [B]`` and
the layer index are scalar-prefetch operands. Row ``b``'s state (``d_k x H *
d_v``: 96 x 5,760 float32 = 2.2 MB at Olmo-Hybrid's shapes) is fetched by DMA
into one of two VMEM buffers while row ``b - 1`` is computed, the new state is
written into one of two more and sent back to the row's slot while row ``b +
1`` is computed. A row of slot 0 (a pad row) is neither read nor written and
returns 0.

The arithmetic is :func:`paddle_tpu.ops.gated_delta.gated_delta_step`'s, in
float32 on the vector unit, in the pool's layout: head ``h`` is the columns
``h * d_v ..`` of the state, and ``k_h`` (and ``q_h``) has to stand in every
one of them. That expansion is a product with a 0/1 matrix ``E [H, H * d_v]``
on the matrix unit, made exact by splitting the float32 operand into three
bfloat16 parts (each product then has one non-zero term, and the three parts
sum back to the operand). The columns are taken in chunks of whole heads
that are whole lane tiles (two heads of 192 = 384 = three tiles) so that no
temporary is larger than a chunk.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_decode_pallas", "supported_shapes"]

LANES = 128


def supported_shapes(pool, heads=None) -> bool:
    """Shapes the compiled kernel takes: a float32 pool ``[L, slots, d_k, H *
    d_v]`` with ``d_k`` whole sublane tiles, ``H * d_v`` whole lane tiles and
    at most 128 heads (the expansion's contraction is one lane tile; None:
    not asked)."""
    dk, width = pool.shape[-2:]
    return (pool.dtype == jnp.float32 and pool.ndim == 4 and dk % 8 == 0
            and width % LANES == 0
            and (heads is None or (heads <= LANES and width % heads == 0)))


def _chunk_width(width: int, dv: int) -> int:
    """Columns a chunk: the fewest whole heads that are whole lane tiles, or
    the whole width where none divides it."""
    cw = dv * LANES // math.gcd(dv, LANES)
    return cw if width % cw == 0 else width


def _split3(x):
    """``x`` float32 as three bfloat16 parts that sum back to it exactly."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _expand(parts, e):
    """``x @ e`` for ``e`` a 0/1 matrix, exactly: a product a part."""
    return sum(jnp.dot(p, e, preferred_element_type=jnp.float32)
               for p in parts)


def _kernel(layer_ref, slots_ref, qt_ref, kt_ref, v_ref, dec_ref, bet_ref,
            e_ref, pool_hbm, o_ref, out_hbm, inbuf, outbuf, sem_in, sem_out,
            *, cw: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    cur = b % 2
    width = inbuf.shape[-1]

    def fetch(row, buf):
        return pltpu.make_async_copy(pool_hbm.at[layer, slots_ref[row]],
                                     inbuf.at[buf], sem_in.at[buf])

    def store(row, buf):
        return pltpu.make_async_copy(outbuf.at[buf],
                                     out_hbm.at[layer, slots_ref[row]],
                                     sem_out.at[buf])

    def live(row):
        return slots_ref[row] != 0

    @pl.when(jnp.logical_and(b == 0, live(0)))
    def _():
        fetch(0, 0).start()

    @pl.when(b + 1 < nb)
    def _():
        nxt = jnp.minimum(b + 1, nb - 1)

        @pl.when(live(nxt))
        def _():
            fetch(nxt, 1 - cur).start()

    @pl.when(b >= 2)
    def _():
        # the buffer this row writes held row b - 2's state on its way out
        old = jnp.maximum(b - 2, 0)

        @pl.when(live(old))
        def _():
            store(old, cur).wait()

    @pl.when(live(b))
    def _():
        fetch(b, cur).wait()
        qs = _split3(qt_ref[0])                  # [d_k, 128]
        ks = _split3(kt_ref[0])
        for c0 in range(0, width, cw):
            cols = pl.ds(c0, cw)
            e = e_ref[:, cols]
            ke = _expand(ks, e)                  # [d_k, cw]
            qe = _expand(qs, e)
            s = inbuf[cur, :, cols] * dec_ref[0, :, cols]
            kv = jnp.sum(s * ke, axis=0, keepdims=True)
            delta = bet_ref[0, :, cols] * (v_ref[0, :, cols] - kv)
            s = s + ke * delta
            o_ref[0, :, cols] = jnp.sum(s * qe, axis=0, keepdims=True)
            outbuf[cur, :, cols] = s
        store(b, cur).start()

    @pl.when(jnp.logical_not(live(b)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(b == nb - 1)
    def _():
        @pl.when(live(b))
        def _():
            store(b, cur).wait()

        @pl.when(nb >= 2)
        def _():
            prev = jnp.maximum(b - 1, 0)

            @pl.when(live(prev))
            def _():
                store(prev, 1 - cur).wait()


@functools.partial(jax.jit, static_argnames=("dv", "interpret"))
def _call(qt, kt, v, dec, bet, pool, slots, layer, e, *, dv, interpret):
    b, dk, hp = qt.shape
    width = pool.shape[-1]
    cw = _chunk_width(width, dv)
    row = lambda i, *_: (i, 0, 0)                      # noqa: E731
    whole = lambda i, *_: (0, 0)                       # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, cw=cw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, dk, hp), row),
                      pl.BlockSpec((1, dk, hp), row),
                      pl.BlockSpec((1, 1, width), row),
                      pl.BlockSpec((1, 1, width), row),
                      pl.BlockSpec((1, 1, width), row),
                      pl.BlockSpec((hp, width), whole),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, 1, width), row),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((2, dk, width), jnp.float32),
                            pltpu.VMEM((2, dk, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, width), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is operand 8 (after the 2 scalar-prefetch operands) and
        # output 1: written where it lies
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # four state buffers (8.8 MB at Olmo-Hybrid's shapes), the
            # expansion matrix double-buffered, a chunk's temporaries
            vmem_limit_bytes=48 * 2 ** 20),
        name="gated_delta_decode",
        interpret=interpret,
    )(layer, slots, qt, kt, v, dec, bet, e, pool)


def expansion(heads: int, dv: int):
    """``E [128, heads * dv]`` bfloat16: 1 where column ``c`` belongs to head
    ``r`` (``c // dv == r``), 0 elsewhere and in the rows past ``heads``."""
    col = jnp.arange(heads * dv) // dv
    return (col[None, :] == jnp.arange(LANES)[:, None]).astype(jnp.bfloat16)


def gated_delta_decode_pallas(q, k, v, g, beta, pool, slots, *, layer=0,
                              interpret: bool = False):
    """``q, k [B, H, d_k]``, ``v [B, H, d_v]``, ``g, beta [B, H]`` float32,
    ``pool [L, slots, d_k, H * d_v]`` float32, ``slots [B]`` -> ``(o [B, H,
    d_v] float32, pool)``; the pool updated in place (the caller donates
    it). ``layer`` may be a traced scalar."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    pad = ((0, 0), (0, 0), (0, LANES - h))
    qt = jnp.pad(jnp.swapaxes(q.astype(jnp.float32), 1, 2), pad)
    kt = jnp.pad(jnp.swapaxes(k.astype(jnp.float32), 1, 2), pad)
    row = (b, 1, h * dv)
    dec = jnp.repeat(jnp.exp(g.astype(jnp.float32)), dv, axis=-1).reshape(row)
    bet = jnp.repeat(beta.astype(jnp.float32), dv, axis=-1).reshape(row)
    o, pool = _call(qt, kt, v.astype(jnp.float32).reshape(row), dec, bet,
                    pool, slots.astype(jnp.int32),
                    jnp.asarray(layer, jnp.int32).reshape(1),
                    expansion(h, dv), dv=dv, interpret=interpret)
    return o.reshape(b, h, dv), pool
