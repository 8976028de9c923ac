"""Block-paged KV cache: device page pool + free-list allocator + host spill.

The PagedAttention idea (vLLM, SOSP'23) applied to our stack: instead of
one contiguous ``[B, max_len, KH, D]`` cache per sequence (whose max_len
reservation wastes ~60-80% of KV memory on real traffic), the KV store
is a pool of fixed-size *blocks* — ``[L, num_blocks, block_size, *row]``
(heads first, ``[L, num_blocks, KH, block_size, D]``, for a row of few heads
that the chip would pad: ``ops/paged_layout.py``) for each kind of row the model caches a token (keys and values a head:
two pools of ``[KH, D]`` rows; a latent-attention model: one pool of its
latent row, no value pool; keys and values fused: one pool of ``[2 * KH, D]``
rows, a page's keys and values contiguous; the model's
``serve_cache_rows()`` says which) —
and each sequence owns an ordered block list. Allocation
is a min-id free list (deterministic: the same request schedule always
produces the same block assignment, which the tests pin), fragmentation
is impossible (every block is the same shape), and capacity pressure is
handled by *preempting* a sequence: its blocks are gathered to host
memory (``framework/offload.py``'s host tier — ``pinned_host`` on TPU,
``unpinned_host`` on CPU where the parity tests run), freed, and later
restored bitwise into freshly allocated blocks.

Block 0 is reserved as the **null sink**: padded table entries point at
it, so the bucketed prefill/decode executables can scatter the KV of
padding tokens somewhere harmless instead of branching on raggedness.
Nothing ever reads block 0 through an attention mask — gathered keys at
positions >= the sequence's context length are masked to -inf before the
softmax (``ops.flash_attention.single_query_attention``).

All pool updates run through jitted scatter/gather helpers that donate
the pool (XLA updates the pages in place — the pool is never copied),
at dispatch level between executables — never a transfer inside a loop
body (rule J012).
"""

from __future__ import annotations

import functools
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..fault.injection import fire as _fault_fire
from ..framework.offload import host_memory_kind
from ..observability import metrics
from ..ops.paged_layout import page_shape

__all__ = ["BlockAllocator", "PagedKVCache", "NULL_BLOCK",
           "OutOfBlocksError", "SpillError"]

# Block id every padded table slot points at (reserved at init).
NULL_BLOCK = 0


class OutOfBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation even after preemption.

    The engine treats this as a **per-request** failure (the sequence that
    needed the block ends FAILED with an F003 Diagnostic); it never
    crosses the engine loop."""


class SpillError(RuntimeError):
    """A host-spill allocation/transfer failed. Surfaced per-request: the
    engine fails the victim sequence (freeing its device blocks) instead
    of crashing the serving loop — host memory pressure costs one
    request's work, not the process."""


class BlockAllocator:
    """Min-id free heap over ``num_blocks`` KV blocks (block 0 reserved).

    Lowest-id-first allocation keeps the assignment deterministic under a
    fixed request schedule and re-uses freed blocks immediately (hot
    pages stay hot). ``alloc`` is all-or-nothing: a partial grant would
    leave the caller holding blocks it cannot use.

    Every allocated block carries a **refcount** (the prefix-sharing
    substrate): ``alloc`` grants refcount 1, :meth:`ref` adds an owner
    (a sequence attaching to a shared prefix page, or the radix tree's
    own cache hold), and :meth:`free` drops one owner — the block
    returns to the free list only when its last owner lets go. With no
    sharing in play every refcount stays at 1 and alloc/free behave
    exactly as the pre-refcount allocator (the flag-off bitwise
    contract); over-freeing past zero is still a hard ``double-free``.
    """

    def __init__(self, num_blocks: int, reserved: Sequence[int] = (NULL_BLOCK,)):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (one is the null sink), "
                             f"got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._reserved = frozenset(int(r) for r in reserved)
        # a sorted list is a valid heap; alloc pops, free pushes: a grant
        # costs its own size, not the pool's
        self._free = sorted(set(range(self.num_blocks)) - self._reserved)
        self._used: set = set()
        self._refs: Dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)

    @property
    def n_shared(self) -> int:
        """Blocks currently held by more than one owner."""
        return sum(1 for r in self._refs.values() if r > 1)

    def refcount(self, i: int) -> int:
        return self._refs.get(int(i), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n lowest free block ids, or None when fewer than n are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        got = [heapq.heappop(self._free) for _ in range(n)]
        self._used.update(got)
        for i in got:
            self._refs[i] = 1
        return got

    def ref(self, ids: Sequence[int]) -> None:
        """Add one owner to each allocated block (prefix-share attach)."""
        ids = [int(i) for i in ids]
        for i in ids:
            if i not in self._used:
                raise ValueError(f"ref of unallocated block {i}")
        for i in ids:
            self._refs[i] += 1

    def free(self, ids: Sequence[int]) -> None:
        """Drop one owner per block; last-owner blocks return to the
        free list."""
        ids = [int(i) for i in ids]
        repeated = len(set(ids)) != len(ids)
        for i in ids:
            if i in self._reserved:
                raise ValueError(f"freeing reserved block {i}")
            if i not in self._used:
                raise ValueError(f"double-free of block {i}")
            if repeated and ids.count(i) > self._refs[i]:
                raise ValueError(
                    f"double-free of block {i} (repeated past its "
                    f"refcount in one free call)")
        for i in ids:
            self._refs[i] -= 1
            if self._refs[i] == 0:
                del self._refs[i]
                self._used.discard(i)
                heapq.heappush(self._free, i)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_blocks(pages, ids, vals):
    """pages[:, ids] = vals, pool donated (in-place under XLA)."""
    return pages.at[:, ids].set(vals)


@jax.jit
def _gather_blocks(pages, ids):
    return pages[:, ids]


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slot(pool, slot, vals):
    """pool[:, slot] = vals, pool donated."""
    return pool.at[:, slot].set(vals)


@jax.jit
def _gather_slot(pool, slot):
    return pool[:, slot]


class PagedKVCache:
    """The device page pool for one model: one array a kind of row the model
    caches for a token, each ``[n_layers, num_blocks, *page]`` with the page
    ``[block_size, *row]`` or, for a keys-or-values row of few heads, heads
    first ``[kv_heads, block_size, head_dim]``.

    ``rows`` is the model's row spec (``model.serve_cache_rows()``): a tuple
    of per-token row shapes, one a pool. A model that caches keys and values
    a head gives ``((kv_heads, head_dim), (kv_heads, head_dim))`` (what
    ``kv_heads``/``head_dim`` alone build; the pools are then also ``.k`` and
    ``.v``); a latent-attention model gives one row of its latent width,
    ``((576,),)``, and has no second pool; a model that caches keys and
    values as one fused row gives ``((2 * kv_heads, head_dim),)`` (keys the
    first half of the heads) and has one pool of the bytes the two would
    take. Block tables, the allocator, spill
    and restore are the same for every spec: they move whole blocks of every
    pool together, and so does the prefix tree; none of them looks inside a
    page.

    **The stored size of a row.** The chip stores an array's last two axes in
    tiles (16 x 128 in bfloat16) and pads each up to whole tiles, so a page
    ``[block_size, 4, 128]`` would hold its 4 heads as 16, four times the
    bytes (the latent row of 576 values is widened to 640 by its model for
    the same reason). ``ops/paged_layout.page_shape`` therefore picks the
    page's layout from the row's shape such that no axis is padded: rows of
    ``(4, 128)`` are stored heads first (the token axis fills the tile); rows
    of ``(16, 128)`` and the latent row keep tokens first, as ever.
    ``bytes_per_block`` is then what a block takes on the device too.

    The pool arrays are owned here but *written* by the serving engine's
    prefill/decode executables, which take them as donated arguments and
    return the updated pools; :meth:`swap` re-homes the references. Spill
    and restore move whole per-sequence block lists between the pools and
    the host memory tier.

    **A second kind of cache: a state a sequence.** A model whose layers
    are of two kinds (rows a token in some, a fixed-size state a sequence in
    others: ``serve_keeps = "state"`` at the engine's seam) gives ``state``,
    the parts of what such a layer keeps (``model.serve_state()``). The page
    pools then span only the row layers (``n_layers`` of them, indexed by
    their order among them), and ``states`` holds one slot pool a part,
    ``[n_state_layers, n_slots, *part]``, over the state layers; a sequence
    holds one slot of all of them, granted by a :class:`BlockAllocator` of
    its own (``slots``; slot 0 is the null sink of pad rows). ``arrays`` is
    what the programs take donated: page pools, then slot pools.
    """

    def __init__(self, n_layers: int, num_blocks: int, block_size: int,
                 kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype=jnp.float32,
                 rows: Optional[Sequence[Sequence[int]]] = None,
                 state: Sequence[jax.ShapeDtypeStruct] = (),
                 n_state_layers: int = 0, n_slots: int = 0):
        if rows is None:
            rows = ((int(kv_heads), int(head_dim)),) * 2
        self.rows = tuple(tuple(int(d) for d in r) for r in rows)
        self.n_layers = int(n_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = jnp.dtype(dtype)
        self.pools = tuple(
            jnp.zeros((n_layers, num_blocks)
                      + page_shape(r, block_size, self.dtype), self.dtype)
            for r in self.rows)
        self.allocator = BlockAllocator(num_blocks)
        #: the slot pools of the layers that keep a state a sequence, one a
        #: part of the state (``[n_state_layers, n_slots, *part]``), and the
        #: allocator of their slots (slot 0 the null sink of pad rows)
        self.states = tuple(
            jnp.zeros((int(n_state_layers), int(n_slots)) + tuple(p.shape),
                      p.dtype) for p in (state if n_state_layers else ()))
        self.slots = BlockAllocator(n_slots) if self.states else None
        self.host_kind = host_memory_kind()

    # the two pools of a keys-and-values spec, by their old names
    @property
    def k(self):
        return self.pools[0]

    @k.setter
    def k(self, value):
        self.pools = (value,) + self.pools[1:]

    @property
    def v(self):
        return self.pools[1]

    @v.setter
    def v(self, value):
        self.pools = self.pools[:1] + (value,) + self.pools[2:]

    @property
    def bytes_per_block(self) -> int:
        per_token = sum(int(np.prod(r)) for r in self.rows)
        return (self.n_layers * self.block_size * per_token
                * self.dtype.itemsize)

    @property
    def bytes_per_slot(self) -> int:
        """What one sequence's state takes over every state layer."""
        return sum(p.nbytes // p.shape[1] for p in self.states)

    @property
    def arrays(self) -> Tuple:
        """Every array the programs take donated: the page pools, then the
        slot pools."""
        return self.pools + self.states

    def swap(self, *arrays) -> None:
        """Adopt the arrays an executable returned (the old ones were donated
        into it): the page pools, then the slot pools."""
        if len(arrays) != len(self.arrays):
            raise ValueError(f"swap of {len(arrays)} pools into "
                             f"{len(self.arrays)}")
        self.pools = tuple(arrays[:len(self.pools)])
        self.states = tuple(arrays[len(self.pools):])

    # -- spill / restore -----------------------------------------------------

    def _to_host(self, x: jax.Array):
        """Commit one gathered stripe to the host memory tier
        (``pinned_host``/``unpinned_host`` sharding when the runtime
        exposes one, plain host numpy otherwise)."""
        if self.host_kind is None:
            return np.asarray(x)
        tgt = x.sharding.with_memory_kind(self.host_kind)
        return jax.device_put(x, tgt)

    def _gather_to_host(self, block_ids: Sequence[int]) -> Tuple:
        ids = jnp.asarray(list(block_ids), jnp.int32)
        return tuple(self._to_host(_gather_blocks(p, ids))
                     for p in self.pools)

    def spill(self, block_ids: Sequence[int]) -> Tuple:
        """Gather ``block_ids`` to host and free them. Returns the opaque
        host tuple (one array a pool) :meth:`restore` takes; the device
        blocks are reusable immediately after.

        A host allocation/transfer failure raises :class:`SpillError`
        (the blocks stay allocated: the caller owns the cleanup); the
        ``serve.mid_spill`` fire point lets the fault drill kill or
        perturb the process inside the spill window, before the blocks
        are freed."""
        try:
            host = self._gather_to_host(block_ids)
            _fault_fire("serve.mid_spill")
            if self.host_kind is not None:
                # Host commit must complete before the blocks are handed
                # out again: a donated overwrite racing the D2H would
                # tear the copy.
                jax.block_until_ready(host)
        except SpillError:
            raise
        except (RuntimeError, MemoryError, ValueError) as e:
            raise SpillError(
                f"host spill of {len(block_ids)} block(s) failed: {e}"
            ) from e
        self.allocator.free(list(block_ids))
        metrics.counter("serving.kv_spills",
                        "sequence KV spills to host memory").inc()
        return host

    def snapshot(self, block_ids: Sequence[int]) -> Tuple:
        """Gather ``block_ids`` to the host tier WITHOUT freeing them:
        the prefix tree's eviction spill (the tree drops its device hold
        separately once the copy is committed) and the drafter pool's
        mirror spill (whose blocks are never allocator-owned). Same
        bitwise round-trip contract as :meth:`spill`."""
        try:
            host = self._gather_to_host(block_ids)
            if self.host_kind is not None:
                jax.block_until_ready(host)
        except (RuntimeError, MemoryError, ValueError) as e:
            raise SpillError(
                f"host snapshot of {len(block_ids)} block(s) failed: {e}"
            ) from e
        return host

    def restore(self, host_kv: Tuple, block_ids: Sequence[int]) -> None:
        """Scatter a spilled tuple into freshly allocated blocks (ids
        may differ from the spilled ones: the block table is rewritten
        by the caller). Bitwise: the round trip is a copy, not a cast."""
        ids = jnp.asarray(list(block_ids), jnp.int32)
        if int(ids.shape[0]) != int(host_kv[0].shape[1]):
            raise ValueError(
                f"restore of {host_kv[0].shape[1]} blocks into "
                f"{ids.shape[0]} ids")
        self.pools = tuple(
            _scatter_blocks(p, ids, jnp.asarray(h, self.dtype))
            for p, h in zip(self.pools, host_kv))
        metrics.counter("serving.kv_restores",
                        "sequence KV restores from host memory").inc()

    # -- the state of one sequence, by slot ------------------------------------

    def spill_state(self, slot: int) -> Tuple:
        """Gather one slot of every slot pool to the host tier and free the
        slot; :meth:`restore_state` takes the tuple back, bitwise."""
        try:
            host = tuple(self._to_host(_gather_slot(p, slot))
                         for p in self.states)
            if self.host_kind is not None:
                jax.block_until_ready(host)
        except (RuntimeError, MemoryError, ValueError) as e:
            raise SpillError(f"host spill of state slot {slot} failed: {e}"
                             ) from e
        self.slots.free([slot])
        return host

    def restore_state(self, host_state: Tuple, slot: int) -> None:
        """Scatter a spilled state into a freshly granted slot."""
        self.states = tuple(
            _scatter_slot(p, slot, jnp.asarray(h, p.dtype))
            for p, h in zip(self.states, host_state))

    def read_state(self, slot: int) -> Tuple[np.ndarray, ...]:
        """Host copies of one slot of every slot pool (tests / debugging)."""
        return tuple(np.asarray(_gather_slot(p, slot)) for p in self.states)

    def read_blocks(self, block_ids: Sequence[int]) -> Tuple[np.ndarray, ...]:
        """Host copies of the given blocks, one array a pool (tests /
        debugging)."""
        ids = jnp.asarray(list(block_ids), jnp.int32)
        return tuple(np.asarray(_gather_blocks(p, ids)) for p in self.pools)
