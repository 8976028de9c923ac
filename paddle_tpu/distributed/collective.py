"""Collective communication API.

Reference design: Python wrappers (``python/paddle/distributed/communication/``)
over C++ ``ProcessGroup`` backends (``fluid/distributed/collective/
process_group.h:53`` — NCCL/Gloo/BKCL/MPI), with collectives-as-ops for static
graphs (``phi/kernels/all_reduce_kernel.h``).

TPU-native design (SURVEY §5): a ProcessGroup facade is the wrong idiom — a
"group" here is a **mesh axis** (or axis tuple) of the hybrid Mesh, and each
collective lowers to the XLA op (``psum``/``all_gather``/``psum_scatter``/
``all_to_all``/``ppermute``) that rides ICI. Two calling conventions, one API:

1. **Inside shard_map/pjit** (the hot path — how parallel layers use it): the
   axis is bound; calls emit the XLA collective directly into the traced
   program, where the compiler schedules/overlaps it (the analog of the
   reference's collective-ops-in-graph design).
2. **Eager** (paddle-parity, host loop): operates on a *stacked-ranks* global
   array whose leading dimension is the group size (how a fake-cluster test
   or a host pipeline holds per-rank values); the call wraps itself in
   shard_map over the group's devices, so it still executes a real XLA
   collective on the mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .topology import get_hybrid_mesh

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "reduce_scatter", "all_to_all", "broadcast", "reduce",
           "scatter", "send", "recv", "ppermute_next", "barrier",
           "in_axis_context", "axis_rank", "world_group", "split_group"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = (mesh, axis name or tuple of axis names)."""

    _next_id = 0

    def __init__(self, mesh: Mesh, axes: Union[str, Sequence[str]],
                 name: Optional[str] = None):
        self.mesh = mesh
        self.axes: Tuple[str, ...] = (axes,) if isinstance(axes, str) else tuple(axes)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh axes {mesh.axis_names}")
        self.name = name or "_".join(self.axes)
        self.id = Group._next_id
        Group._next_id += 1

    @property
    def axis_name(self) -> Union[str, Tuple[str, ...]]:
        return self.axes[0] if len(self.axes) == 1 else self.axes

    @property
    def nranks(self) -> int:
        n = 1
        for a in self.axes:
            n *= self.mesh.shape[a]
        return n

    world_size = nranks

    @property
    def rank(self) -> int:
        """Host-side rank of this *process* within the group: the mesh
        coordinate of the process's first local device along the group axes,
        flattened. Single-controller (all devices local) this is 0 — use
        ``axis_rank`` inside a trace for per-device rank. Multi-controller
        this is the true process rank along the group axes."""
        first_local = None
        for d in self.mesh.devices.flat:
            if d.process_index == jax.process_index():
                first_local = d
                break
        if first_local is None:
            return 0
        idx = np.argwhere(self.mesh.devices == first_local)
        if idx.size == 0:
            return 0
        coord = dict(zip(self.mesh.axis_names, idx[0]))
        rank = 0
        for a in self.axes:
            rank = rank * self.mesh.shape[a] + int(coord[a])
        return rank

    def process_ids(self):
        return list(range(self.nranks))

    ranks = property(process_ids)

    def __repr__(self):
        return f"Group(axes={self.axes}, nranks={self.nranks})"


_groups = {}


def _default_mesh() -> Mesh:
    mesh = get_hybrid_mesh()
    if mesh is None:
        # Implicit world mesh over all devices on one axis.
        devs = np.asarray(jax.devices())
        mesh = Mesh(devs, axis_names=("world",))
        from .topology import set_hybrid_mesh
        set_hybrid_mesh(mesh)
    return mesh


def world_group() -> Group:
    mesh = _default_mesh()
    return Group(mesh, mesh.axis_names)


def new_group(ranks=None, backend=None, axes=None, mesh=None) -> Group:
    """Parity shim for paddle.distributed.new_group.

    TPU-native groups are mesh axes: pass ``axes=`` (and optionally ``mesh=``).
    Arbitrary rank subsets (supported by NCCL communicators in the reference)
    do not map onto mesh collectives; only full-axis groups are supported —
    callers needing rank subsets should add a mesh axis that factors them.
    """
    mesh = mesh or _default_mesh()
    if axes is not None:
        g = Group(mesh, axes)
    elif ranks is None or len(ranks) == jax.device_count():
        g = Group(mesh, mesh.axis_names)
    else:
        raise NotImplementedError(
            "new_group(ranks=<subset>) has no mesh-axis equivalent; create "
            "the hybrid mesh with an axis for this group instead "
            "(fleet.init(strategy) does this for dp/mp/pp/sharding/sep).")
    _groups[g.id] = g
    return g


def get_group(gid: int) -> Group:
    return _groups[gid]


def split_group(group: Group, axis: str) -> Group:
    return Group(group.mesh, axis)


# ---------------------------------------------------------------------------
# Axis-context detection
# ---------------------------------------------------------------------------

def in_axis_context(axes: Union[str, Tuple[str, ...]]) -> bool:
    """True if called inside shard_map/pmap with these axes bound."""
    axes = (axes,) if isinstance(axes, str) else axes
    try:
        for a in axes:
            lax.axis_index(a)
        return True
    except (NameError, Exception):
        return False


def axis_rank(group: Optional[Group] = None) -> jax.Array:
    """Rank of the current shard along the group axis (inside shard_map)."""
    g = group or world_group()
    idx = lax.axis_index(g.axes[0])
    mult = 1
    for a in g.axes[1:]:
        idx = idx * g.mesh.shape[a] + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Eager fallback plumbing: stacked-ranks layout over the group's axes.
# ---------------------------------------------------------------------------

def _eager_run(group: Group, fn, x, out_has_rank_dim: bool = True):
    """Run per-shard `fn` over a stacked-ranks array x (leading dim ==
    group.nranks): shard x's leading dim over the group axes, apply fn in
    shard_map (real XLA collective over the mesh devices), return the results
    re-stacked along the rank dim — same layout in, same layout out."""
    shard_map = jax.shard_map
    mesh = group.mesh
    n = group.nranks
    x = jnp.asarray(x)
    if x.shape[0] != n:
        raise ValueError(
            f"eager collective expects leading dim == group size {n}, "
            f"got shape {x.shape}")
    # Reshape leading dim into the group's axes; other mesh axes replicate.
    k = len(group.axes)
    axes_shape = tuple(mesh.shape[a] for a in group.axes)
    xr = x.reshape(axes_shape + x.shape[1:])
    io_spec = P(*group.axes, *([None] * (x.ndim - 1)))

    def wrapped(xs):
        # xs carries the group axes as leading singleton dims; strip them.
        for _ in range(k):
            xs = jnp.squeeze(xs, axis=0)
        out = fn(xs)
        for _ in range(k):
            out = out[None]
        return out

    f = shard_map(wrapped, mesh=mesh, in_specs=(io_spec,),
                  out_specs=io_spec, check_vma=False)
    out = jax.jit(f)(xr)
    return out.reshape((n,) + out.shape[k:])


# ---------------------------------------------------------------------------
# Collectives
# ---------------------------------------------------------------------------

def _reduce_in_ctx(x, op: str, axes):
    if op == ReduceOp.SUM:
        return lax.psum(x, axes)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axes)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axes)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axes)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x), axes))
    raise ValueError(op)


def all_reduce(x, op: str = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True):
    """paddle.distributed.all_reduce parity."""
    g = group or world_group()
    if in_axis_context(g.axes):
        return _reduce_in_ctx(x, op, g.axis_name)
    out = _eager_run(g, lambda s: _reduce_in_ctx(s, op, g.axis_name), x,
                     out_has_rank_dim=True)
    return out


def all_gather(x, group: Optional[Group] = None, axis: int = 0,
               tiled: bool = True):
    """Concatenate shards along `axis` (stream.all_gather semantics)."""
    g = group or world_group()
    if in_axis_context(g.axes):
        return lax.all_gather(x, g.axis_name, axis=axis, tiled=tiled)
    return _eager_run(
        g, lambda s: lax.all_gather(s, g.axis_name, axis=axis, tiled=tiled),
        x, out_has_rank_dim=True)


def reduce_scatter(x, op: str = ReduceOp.SUM, group: Optional[Group] = None,
                   scatter_axis: int = 0):
    """Sum across ranks then scatter slices along scatter_axis."""
    g = group or world_group()
    if op != ReduceOp.SUM:
        raise NotImplementedError("reduce_scatter supports SUM")
    if in_axis_context(g.axes):
        return lax.psum_scatter(x, g.axis_name, scatter_dimension=scatter_axis,
                                tiled=True)
    return _eager_run(
        g, lambda s: lax.psum_scatter(s, g.axis_name,
                                      scatter_dimension=scatter_axis, tiled=True),
        x, out_has_rank_dim=True)


def all_to_all(x, group: Optional[Group] = None, split_axis: int = 0,
               concat_axis: int = 0):
    """Each rank splits x along split_axis into nranks chunks and exchanges
    (ref: communication/all_to_all.py; MoE global_scatter/gather building
    block)."""
    g = group or world_group()
    if in_axis_context(g.axes):
        return lax.all_to_all(x, g.axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    return _eager_run(
        g, lambda s: lax.all_to_all(s, g.axis_name, split_axis=split_axis,
                                    concat_axis=concat_axis, tiled=True),
        x, out_has_rank_dim=True)


def broadcast(x, src: int = 0, group: Optional[Group] = None):
    g = group or world_group()

    def bcast(s):
        gathered = lax.all_gather(s, g.axis_name, axis=0, tiled=False)
        return gathered[src]

    if in_axis_context(g.axes):
        return bcast(x)
    return _eager_run(g, bcast, x, out_has_rank_dim=True)


def reduce(x, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None):
    """Result is the reduction on every rank (superset of paddle's dst-only
    guarantee; XLA has no cheaper dst-only form on ICI)."""
    return all_reduce(x, op, group)


def scatter(x, src: int = 0, group: Optional[Group] = None, axis: int = 0):
    g = group or world_group()

    def scat(s):
        gathered = lax.all_gather(s, g.axis_name, axis=0, tiled=False)
        full = gathered[src]
        n = g.nranks
        idx = axis_rank(g)
        chunk = full.shape[axis] // n
        return lax.dynamic_slice_in_dim(full, idx * chunk, chunk, axis)

    if in_axis_context(g.axes):
        return scat(x)
    return _eager_run(g, scat, x, out_has_rank_dim=True)


def ppermute_next(x, group: Optional[Group] = None, shift: int = 1):
    """Ring shift along the group axis (the ICI-native p2p primitive; used by
    pipeline & ring attention). Inside shard_map only."""
    g = group or world_group()
    n = g.nranks
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, g.axis_name, perm)


def send(x, dst: int, group: Optional[Group] = None):
    """Point-to-point on TPU is a collective-permute; arbitrary send/recv
    pairs should be expressed as ppermute patterns (see p2p module)."""
    raise NotImplementedError(
        "Use paddle_tpu.distributed.p2p (ppermute-based) inside shard_map; "
        "eager raw send/recv has no XLA/ICI equivalent.")


recv = send


def barrier(group: Optional[Group] = None):
    g = group or world_group()
    if in_axis_context(g.axes):
        return lax.psum(jnp.ones(()), g.axis_name)
    x = jnp.ones((g.nranks, 1))
    _eager_run(g, lambda s: lax.psum(s, g.axis_name), x, out_has_rank_dim=True)
    return None
