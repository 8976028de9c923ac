"""How a page of the serving pool is laid out, and the reads and writes that
depend on it.

A page holds ``block_size`` tokens of one layer; a token's row is what the
model caches for it (``model.serve_cache_rows()``). The TPU stores an array's
last two axes in tiles of ``(32 // itemsize, 128)`` (8 x 128 for float32,
16 x 128 for bfloat16) and pads each up to whole tiles, so the layout is
chosen from the row's shape such that no axis is padded on the chip:

- **tokens first**, ``[block_size, *row]``: a latent row ``(W,)`` (the tile
  is ``block_size x W``) and a keys-or-values row ``(KH, D)`` whose ``KH``
  fills whole sublane tiles (16 heads in bfloat16);
- **heads first**, ``[KH, block_size, D]``: a row ``(KH, D)`` whose ``KH``
  does not fill a sublane tile while ``block_size`` does and ``D`` fills
  whole lanes (4 heads of 128 in bfloat16: stored tokens-first every head
  axis of 4 would be padded to 16, four times the bytes). The token axis is
  then inside the tile, and one head's keys of a page are contiguous;
- **a fused row**, keys and values as ONE row of ``2 * KH`` heads (keys the
  first ``KH``, values the rest; :func:`split_keys_values`): the same rule on
  its shape, so 8 heads of 128 in bfloat16 are a heads-first page ``[2 * KH,
  block_size, D]``. One pool then holds what two did, in the same bytes, and
  a page's keys AND values are one contiguous stretch: one DMA descriptor
  where two pools take two (``_pallas/block_paged_attention.py``).

A pool is ``[layers, blocks, *page]``. Which layout a five-axis pool has is
read back from its shape and the block size (the two orders differ in where
``block_size`` stands; where ``KH == block_size`` the rule above never picks
heads first).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp

__all__ = ["page_shape", "heads_first", "write_blocks", "write_tokens",
           "gather_pages", "split_keys_values"]


def page_shape(row: Sequence[int], block_size: int, dtype) -> Tuple[int, ...]:
    """The shape of one page of rows ``row`` (see the module docstring)."""
    row = tuple(int(d) for d in row)
    sublane = 32 // jnp.dtype(dtype).itemsize
    if (len(row) == 2 and row[0] % sublane and block_size % sublane == 0
            and row[1] % 128 == 0):
        return (row[0], block_size, row[1])
    return (block_size,) + row


def heads_first(pool, block_size: int) -> bool:
    """Whether ``pool`` (``[layers, blocks, *page]``, or one layer's
    ``[blocks, *page]`` of a keys-or-values row) has its pages heads first."""
    a, b = pool.shape[-3], pool.shape[-2]
    return b == block_size and a != block_size


def write_blocks(pool, layer, block_ids, rows, block_size: int):
    """Whole pages: ``rows [len(block_ids) * block_size, *row]`` (tokens in
    order) into the pages ``block_ids`` of ``layer``."""
    n = block_ids.shape[0]
    vals = rows.reshape((n, block_size) + rows.shape[1:]).astype(pool.dtype)
    if pool.ndim == 5 and heads_first(pool, block_size):
        vals = vals.transpose(0, 2, 1, 3)
    return pool.at[layer, block_ids].set(vals)


def write_tokens(pool, layer, bi, si, rows, block_size: int):
    """Single tokens: ``rows [..., *row]`` into slot ``si [...]`` of page
    ``bi [...]`` of ``layer``."""
    rows = rows.astype(pool.dtype)
    if pool.ndim == 5 and heads_first(pool, block_size):
        # every leading axis indexed (the head by its own number): the
        # update's window is the trailing ``D`` alone, a scatter XLA does in
        # place; a slice between two index arrays would make it transpose
        # the whole pool first
        heads = jnp.arange(pool.shape[2])
        return pool.at[layer, bi[..., None], heads, si[..., None]].set(rows)
    return pool.at[layer, bi, si].set(rows)


def gather_pages(pool, tables, block_size: int):
    """One layer's pool ``[blocks, *page]`` read through ``tables [B, M]``:
    ``[B, M * block_size, *row]``, tokens in order, whatever the layout."""
    b, m = tables.shape
    got = pool[tables]
    if pool.ndim == 4 and heads_first(pool, block_size):
        got = got.transpose(0, 1, 3, 2, 4)
    return got.reshape((b, m * block_size) + got.shape[3:])


def split_keys_values(rows):
    """A fused row ``[..., 2 * KH, D]`` (or gathered rows of it) as ``(keys,
    values)``, each ``[..., KH, D]``: keys are the first half of the heads."""
    return jnp.split(rows, 2, axis=-2)
