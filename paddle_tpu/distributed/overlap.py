"""Communication-overlap tier: decomposed collectives + prefetch disciplines.

The hybrid-parallel step (``framework/sharded.py``) hands every collective
to GSPMD and *hopes* XLA overlaps it. Three classes of critical-path
communication get explicit overlap structure here, all behind
``FLAGS_comm_overlap`` (default ``off`` — byte-identical to the GSPMD
path until a measured win flips the default):

**Decomposed collective matmul** (Wang et al., "Overlapping Communication
with Dependent Computation via Decomposition in Large Deep Learning
Models", ASPLOS 2023 — the TPU collective-matmul work). A Megatron-SP
layer pass moves one all-gather and one reduce-scatter of the activation
tensor per direction; issued as single collectives they sit on the
critical path in front of / behind the matmul that consumes/produces
them. Decomposition rewrites

- ``all_gather(x) @ w``  as a **bidirectional** ``lax.ppermute`` ring: the
  local seq-chunk's partial matmul runs while both neighbours' chunks are
  in flight (one hop clockwise, one counter-clockwise per step — the
  traffic pattern bidirectional ICI links are built for), so every hop's
  transfer hides under the previous chunk's matmul
  (:func:`allgather_matmul`);
- ``reduce_scatter(x @ w)`` as the mirrored ring: per-destination-chunk
  partial products are computed one hop ahead of the travelling
  accumulators (payload split in half across the two directions, so the
  per-direction volume — and the volume total — exactly matches the ring
  collective) (:func:`matmul_reduce_scatter`).

The loops are **unrolled** (the hop count is static and small), not
``lax.scan``: XLA's latency-hiding scheduler can only overlap the async
collective-permute start/done of hop *t+1* with hop *t*'s matmul when
both live in one straight-line block — a While body would serialize them.
A chunk-count knob (``chunks`` sub-pieces per hop matmul) controls the
scheduler's interleave granularity; :func:`pick_chunks` takes it from
``FLAGS_comm_overlap_chunks`` or, per (op, mesh, shape), from the
persistent kernel cache (``ops/_pallas/autotune.py``).

**ZeRO-3 gather-ahead** (:func:`zero_gather_ahead`). GSPMD gathers
fsdp-sharded params at first use — nothing is in flight ahead of the
consumer. The same async-dispatch overlap pattern ``framework/offload.py``
proved for host streaming applies in-graph: issue block *i+1*'s param
all-gather (a sharding constraint dropping the fsdp axis) *before* block
*i*'s compute, ordered by an ``optimization_barrier`` chain so gathers
pipeline front-to-back with a bounded ``depth`` ahead of consumption.

**DP gradient-bucket overlap** (:class:`BucketedGradReducer`). The
manual-sharding path (shard_map step code, the eager hybrid-parallel
loop) reduces grads per parameter — dozens of latency-bound collectives
the scheduler cannot overlap (rule J014 lints exactly that). Size-bucketed
reduction concatenates grads into ~``bucket_bytes`` flat buffers and
reduces bucket-by-bucket, so bucket *k*'s reduce-scatter/all-reduce rides
ICI while the remaining backward segments (and later buckets' packing)
still execute — the reference's ``EagerReducer`` discipline
(``collective/reducer.h:88``), expressed over ``lax.psum``/
``lax.psum_scatter``.

Every decomposed loop is statically accounted (hop count × bytes vs the
ICI budget) by :mod:`paddle_tpu.analysis.comm_check` at trace time and
instrumented as a telemetry ``comm`` phase / ``comm/*`` trace span at
dispatch level (``observability/step_monitor.py``).

Built on ``jax.shard_map`` with the decomposed axis manual and every
other mesh axis left to GSPMD (partial-auto).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.comm_check import ALLGATHER_MATMUL, MATMUL_REDUCE_SCATTER
from ..core.flags import flag

__all__ = [
    "overlap_mode", "tp_enabled", "zero_enabled", "dp_enabled",
    "shard_map_compat", "can_decompose",
    "allgather_matmul", "matmul_reduce_scatter",
    "pick_chunks",
    "spec_without_axis", "zero_gather_ahead", "gather_ahead_plan",
    "BucketedGradReducer", "MP_AXIS", "GATHER_AHEAD_DEPTH",
    "SP_COMM_SPECS",
]

MP_AXIS = "mp"

# The CommSpec names this module's decomposed SP/TP pipelines register
# (canonical values in ``analysis.comm_check``) — the step pipeline's
# ``sp_decompose`` pass contract consumes this tuple, so the trace-level
# G003 ownership check follows these call sites by construction.
SP_COMM_SPECS = (ALLGATHER_MATMUL, MATMUL_REDUCE_SCATTER)

# How many blocks of fsdp-sharded params may have their all-gather issued
# ahead of the block currently computing (the prefetch window of the
# optimization_barrier chain in zero_gather_ahead).
GATHER_AHEAD_DEPTH = 2

# ---------------------------------------------------------------------------
# Mode plumbing
# ---------------------------------------------------------------------------

def overlap_mode() -> str:
    """Current ``FLAGS_comm_overlap`` value: off | tp | tp_zero | all."""
    return str(flag("comm_overlap"))


def tp_enabled() -> bool:
    """Decomposed collective matmul active (tp, tp_zero and all)."""
    return overlap_mode() in ("tp", "tp_zero", "all")


def zero_enabled() -> bool:
    """ZeRO-3 gather-ahead active (tp_zero and all)."""
    return overlap_mode() in ("tp_zero", "all")


def dp_enabled() -> bool:
    """DP gradient-bucket overlap active (all only)."""
    return overlap_mode() == "all"


# ---------------------------------------------------------------------------
# shard_map compat + capability gate
# ---------------------------------------------------------------------------

def shard_map_compat(fn: Callable, mesh, in_specs, out_specs,
                     axis_names) -> Callable:
    """``jax.shard_map`` with ``axis_names`` manual, the rest auto.

    Varying-manual-axes checking is off: the decomposed loops build
    their accumulators with ``jnp.zeros`` (unvarying until the first
    ppermute'd write), which strict vma tracking rejects without pcast
    noise on every init."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(axis_names),
                         check_vma=False)


def _ambient_manual() -> bool:
    from .context_parallel import _ambient_manual_axes
    return bool(_ambient_manual_axes())


def can_decompose(mesh, axis: str = MP_AXIS) -> bool:
    """Is the decomposed ppermute pipeline usable on this mesh/axis here?

    Requires the axis with degree > 1 and no enclosing manual shard_map
    (nested manual rings belong to the context-parallel path).
    """
    if mesh is None or axis not in mesh.axis_names:
        return False
    if mesh.shape[axis] <= 1:
        return False
    if _ambient_manual():
        return False
    return True


def _mesh_or_hybrid(mesh):
    if mesh is not None:
        return mesh
    from .topology import get_hybrid_mesh
    return get_hybrid_mesh()


def _is_tracer(*xs) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in xs)


# ---------------------------------------------------------------------------
# Accounting + telemetry hooks (host-side, trace/dispatch time only)
# ---------------------------------------------------------------------------

def _account(op: str, spec, *operands) -> None:
    """Static ICI accounting (analysis.comm_check) + telemetry counters for
    one decomposed call site. Runs on the host at trace time — zero cost
    inside the compiled program. enforce() also RECORDS the spec into any
    active comm_check.recording(), so a step traced under the plan
    verifier sees exactly the hop plans its jaxpr contains (plan_check
    S001/S002); emission still follows FLAGS_static_analysis."""
    from ..analysis import comm_check
    comm_check.enforce(spec, where=f"overlap.{op}")
    from ..observability.trace import telemetry_mode
    if telemetry_mode() != "off":
        from ..observability import metrics
        metrics.counter(
            "comm.decomposed_calls",
            "decomposed collective-matmul call sites traced").labels(
                op=op).inc()


def _comm_span(op: str, spec, *operands):
    """A ``comm/<op>`` trace span for an *eager* decomposed dispatch (the
    hop loop is in-graph; per-call attrs carry the static hop plan),
    under ``FLAGS_telemetry=trace`` only: per-hop detail is for a deep
    dive, not for the default ring. Inside a trace (operands are tracers)
    there is no dispatch to span."""
    from ..observability import trace
    if _is_tracer(*operands) or not trace.tracing_active():
        return trace.NOOP
    return trace.span(f"comm/{op}", hops=spec.hops,
                      bytes_per_hop=spec.bytes_per_hop,
                      axis_size=spec.axis_size)


# ---------------------------------------------------------------------------
# Chunk count (flag, else the persistent cache)
# ---------------------------------------------------------------------------

def _chunks_key(op: str, n: int, x_shape, w_shape, dtype) -> str:
    return (f"{op}|n{n}|x{'x'.join(str(int(d)) for d in x_shape)}"
            f"|w{'x'.join(str(int(d)) for d in w_shape)}|{dtype}")


def pick_chunks(op: str, n: int, x_shape, w_shape, dtype,
                s_local: int) -> int:
    """Sub-chunk count per hop matmul: ``FLAGS_comm_overlap_chunks`` if
    forced, else the persistent autotune cache's winner, else 1."""
    forced = int(flag("comm_overlap_chunks"))
    if forced > 0:
        return forced if s_local % forced == 0 else 1
    from ..ops._pallas.autotune import get_cache
    cfg = get_cache().get("comm_overlap",
                          _chunks_key(op, n, x_shape, w_shape, dtype))
    if isinstance(cfg, dict):
        c = int(cfg.get("chunks", 1))
        if c > 0 and s_local % c == 0:
            return c
    return 1


# ---------------------------------------------------------------------------
# Decomposed collective matmul
# ---------------------------------------------------------------------------

def allgather_matmul(x, w, b=None, *, mesh=None, axis: str = MP_AXIS,
                     chunks: Optional[int] = None):
    """``all_gather(x, seq) @ w`` as a bidirectional ppermute pipeline.

    ``x``: global ``[B, S, K]`` with S sharded over ``axis``; ``w``:
    ``[K, M]`` with M sharded over ``axis`` (column-parallel); ``b``
    optional ``[M]`` sharded like w's columns. Returns ``[B, S, M]`` with
    M sharded — the Megatron-SP column forward, with every ICI hop's
    chunk transfer hidden under the previous chunk's partial matmul.

    Hop schedule (rank r, n ranks): the local chunk's matmul runs first;
    the forward ring (receive from r+1) delivers chunks ``r+1 … r+⌈(n-1)/2⌉``
    and the backward ring chunks ``r-1 … r-⌊(n-1)/2⌋`` — n-1 distinct
    chunk transfers total, the same volume as one ring all-gather, on two
    ICI directions at once.
    """
    mesh = _mesh_or_hybrid(mesh)
    n = mesh.shape[axis]
    if x.ndim != 3 or x.shape[1] % n or w.shape[-1] % n:
        raise ValueError(
            f"allgather_matmul needs x [B, S, K] with S % {n} == 0 and "
            f"w [K, M] with M % {n} == 0; got x {x.shape}, w {w.shape}")
    s_local = x.shape[1] // n
    c = chunks if chunks is not None else pick_chunks(
        "allgather_matmul", n, x.shape, w.shape, str(x.dtype), s_local)
    if s_local % c:
        c = 1
    nf = n // 2            # forward-ring hops (receive from rank+1 side)
    nb = (n - 1) // 2      # backward-ring hops

    from ..analysis import comm_check
    spec = comm_check.spec_for_allgather_matmul(
        x.shape[0], s_local, x.shape[2], w.shape[-1] // n, n,
        jnp.dtype(x.dtype).itemsize, c, axis=axis)
    _account("allgather_matmul", spec, x, w)

    def fn(x_l, w_l, b_l, ranks):
        # rank from a sharded arange, NOT lax.axis_index: axis_index
        # lowers to PartitionId, which partial-auto meshes reject.
        rank = ranks[0]
        bsz, s, _ = x_l.shape

        def write(y, chunk, src):
            # the hop's matmul, in `c` sub-pieces: finer grains for the
            # latency-hiding scheduler to interleave with the transfer
            piece = s // c
            for j in range(c):
                part = lax.dynamic_slice_in_dim(chunk, j * piece, piece, 1)
                y = lax.dynamic_update_slice(
                    y, part @ w_l, (0, src * s + j * piece, 0))
            return y

        y = jnp.zeros((bsz, s * n, w_l.shape[-1]), x_l.dtype)
        y = write(y, x_l, rank)
        perm_fwd = [(i, (i - 1) % n) for i in range(n)]  # recv from r+1
        perm_bwd = [(i, (i + 1) % n) for i in range(n)]  # recv from r-1
        fwd = bwd = x_l
        # Unrolled on purpose: hop t+1's ppermute and hop t's matmul are
        # independent in straight-line code, so XLA overlaps them; a scan
        # body would serialize transfer and compute per iteration.
        for t in range(1, nf + 1):
            fwd = lax.ppermute(fwd, axis, perm_fwd)   # holds chunk r+t
            y = write(y, fwd, (rank + t) % n)
            if t <= nb:
                bwd = lax.ppermute(bwd, axis, perm_bwd)  # holds chunk r-t
                y = write(y, bwd, (rank - t) % n)
        if b_l is not None:
            y = y + b_l
        return y

    ranks = jnp.arange(n, dtype=jnp.int32)
    with _comm_span("allgather_matmul", spec, x, w):
        if b is None:
            return shard_map_compat(
                lambda x_l, w_l, r: fn(x_l, w_l, None, r), mesh,
                (P(None, axis, None), P(None, axis), P(axis)),
                P(None, None, axis), {axis})(x, w, ranks)
        return shard_map_compat(
            fn, mesh,
            (P(None, axis, None), P(None, axis), P(axis), P(axis)),
            P(None, None, axis), {axis})(x, w, b, ranks)


def matmul_reduce_scatter(x, w, b=None, *, mesh=None, axis: str = MP_AXIS,
                          chunks: Optional[int] = None):
    """``reduce_scatter(x @ w, seq)`` as a bidirectional ppermute pipeline.

    ``x``: global ``[B, S, K]`` with K sharded over ``axis`` (row-parallel
    input); ``w``: ``[K, M]`` with K sharded; ``b`` optional replicated
    ``[M]``. Returns ``[B, S, M]`` with S sharded — the Megatron-SP row
    forward. Each travelling accumulator picks up one rank's partial
    product per hop; the output features are split in half across the two
    ring directions, so total volume equals the ring reduce-scatter's.
    """
    mesh = _mesh_or_hybrid(mesh)
    n = mesh.shape[axis]
    if x.ndim != 3 or x.shape[1] % n or x.shape[-1] % n:
        raise ValueError(
            f"matmul_reduce_scatter needs x [B, S, K] with S % {n} == 0 "
            f"and K % {n} == 0; got x {x.shape}")
    s = x.shape[1] // n
    c = chunks if chunks is not None else pick_chunks(
        "matmul_reduce_scatter", n, x.shape, w.shape, str(x.dtype), s)
    if s % c:
        c = 1

    from ..analysis import comm_check
    spec = comm_check.spec_for_matmul_reduce_scatter(
        x.shape[0], s, x.shape[2] // n, w.shape[-1], n,
        jnp.dtype(x.dtype).itemsize, c, axis=axis)
    _account("matmul_reduce_scatter", spec, x, w)

    def fn(x_l, w_l, b_full, ranks):
        rank = ranks[0]
        bsz = x_l.shape[0]
        m = w_l.shape[-1]
        if n == 1:
            y = x_l @ w_l
            return y + b_full if b_full is not None else y
        h = m // 2 if m >= 2 else m

        def partial(chunk_idx, w_half):
            rows = lax.dynamic_slice_in_dim(x_l, chunk_idx * s, s, 1)
            if c == 1:
                return rows @ w_half
            piece = s // c
            outs = [lax.dynamic_slice_in_dim(rows, j * piece, piece, 1)
                    @ w_half for j in range(c)]
            return jnp.concatenate(outs, axis=1)

        w1, w2 = w_l[:, :h], w_l[:, h:]
        # fwd ring sends right: chunk schedule c_t(r) = (r + n-1-t) % n,
        # ending on chunk r at t = n-1; bwd mirrors it leftwards. Each
        # accumulator carries HALF the output features, so both ICI
        # directions move (n-1)/n of half the payload — ring-RS volume.
        acc_f = partial((rank + n - 1) % n, w1)
        acc_b = partial((rank + 1) % n, w2) if h < m else None
        perm_right = [(i, (i + 1) % n) for i in range(n)]
        perm_left = [(i, (i - 1) % n) for i in range(n)]
        for t in range(1, n):
            acc_f = lax.ppermute(acc_f, axis, perm_right)
            acc_f = acc_f + partial((rank + n - 1 - t) % n, w1)
            if acc_b is not None:
                acc_b = lax.ppermute(acc_b, axis, perm_left)
                acc_b = acc_b + partial((rank + 1 + t) % n, w2)
        y = acc_f if acc_b is None else jnp.concatenate([acc_f, acc_b],
                                                        axis=-1)
        if b_full is not None:
            y = y + b_full
        return y

    ranks = jnp.arange(n, dtype=jnp.int32)
    with _comm_span("matmul_reduce_scatter", spec, x, w):
        if b is None:
            return shard_map_compat(
                lambda x_l, w_l, r: fn(x_l, w_l, None, r), mesh,
                (P(None, None, axis), P(axis, None), P(axis)),
                P(None, axis, None), {axis})(x, w, ranks)
        return shard_map_compat(
            fn, mesh,
            (P(None, None, axis), P(axis, None), P(), P(axis)),
            P(None, axis, None), {axis})(x, w, b, ranks)


# ---------------------------------------------------------------------------
# ZeRO-3 gather-ahead
# ---------------------------------------------------------------------------

def spec_without_axis(spec: P, axis: str) -> P:
    """PartitionSpec with every occurrence of ``axis`` removed (the
    gathered view of an fsdp-sharded parameter)."""
    entries = []
    for e in spec:
        if e is None:
            entries.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != axis)
            entries.append(kept if len(kept) > 1
                           else kept[0] if kept else None)
        else:
            entries.append(None if e == axis else e)
    return P(*entries)


@jax.custom_vjp
def _ordered_after(x, anchor):
    """Identity on ``x`` whose forward schedule cannot start before
    ``anchor`` exists (optimization_barrier tie). AD-transparent: the
    barrier orders the forward gathers only — ``optimization_barrier``
    has no differentiation rule, and the backward pass re-gathers in its
    own (reverse) order anyway."""
    return lax.optimization_barrier((x, anchor))[0]


def _ordered_fwd(x, anchor):
    return _ordered_after(x, anchor), None


def _ordered_bwd(res, g):
    return (g, None)  # None = symbolic zero cotangent for the anchor


_ordered_after.defvjp(_ordered_fwd, _ordered_bwd)


def gather_ahead_plan(param_names: Sequence[str],
                      gathered_specs: Dict[str, Any],
                      depth: int = GATHER_AHEAD_DEPTH):
    """The declared ordering plan of :func:`zero_gather_ahead` for the
    step-plan verifier (``analysis/plan_check.py``): which stream blocks
    carry gathered params and the optimization_barrier edges tying block
    *i*'s gather into block *i - depth*'s. Mirrors the anchor logic of
    the traced function exactly — a drift between the two is precisely
    what plan_check rule D003 exists to catch."""
    from ..analysis.plan_check import GatherPlan
    from ..framework.offload import group_by_block
    groups = group_by_block(list(param_names))
    anchored: List[bool] = []
    edges: List[Tuple[int, int]] = []
    gparams: Dict[str, Any] = {}
    for gi, (_, names) in enumerate(groups):
        has = any(n in gathered_specs for n in names)
        if has and gi >= depth and anchored[gi - depth]:
            edges.append((gi - depth, gi))
        anchored.append(has)
        for n in names:
            if n in gathered_specs:
                gparams[n] = gathered_specs[n]
    return GatherPlan(depth=depth, anchored=tuple(anchored),
                      edges=tuple(edges), params=gparams)


def zero_gather_ahead(params: Dict[str, jax.Array],
                      gathered_specs: Dict[str, P], mesh,
                      depth: int = GATHER_AHEAD_DEPTH) -> Dict[str, Any]:
    """Issue per-block param all-gathers ahead of consumption (in-graph).

    For each transformer block (``framework.offload.group_by_block``
    grouping), the fsdp-sharded params are re-constrained to their
    gathered spec; an ``optimization_barrier`` chain ties block *i*'s
    gather into block *i - depth*'s, so XLA must issue the gathers
    front-to-back, pipelined ``depth`` blocks ahead of the consumer —
    block i+1's all-gather rides ICI while block i computes, instead of
    stalling at first use. Semantically the identity (parity is exact up
    to resharding-point float reassociation).
    """
    from ..framework.offload import group_by_block
    groups = group_by_block(list(params))
    out: Dict[str, Any] = dict(params)
    anchors: List[Optional[jax.Array]] = []
    for gi, (_, names) in enumerate(groups):
        anchor = None
        for nm in names:
            v = params[nm]
            gspec = gathered_specs.get(nm)
            if gspec is None:
                continue
            g = lax.with_sharding_constraint(
                v, NamedSharding(mesh, gspec))
            if gi >= depth and anchors[gi - depth] is not None:
                g = _ordered_after(g, anchors[gi - depth])
            out[nm] = g
            if anchor is None:
                anchor = g
        anchors.append(anchor)
    return out


# ---------------------------------------------------------------------------
# DP gradient buckets
# ---------------------------------------------------------------------------

class BucketedGradReducer:
    """Size-bucketed gradient reduction for the manual-sharding path.

    Groups parameters (in their given order — grads finalize back-to-front
    of the model, so callers should pass reversed model order to overlap
    with the earliest available grads) into ~``bucket_bytes`` buckets;
    each bucket reduces as ONE flat collective. Inside ``shard_map`` use
    :meth:`reduce_in_axis` (per-bucket ``lax.psum`` /
    ``lax.psum_scatter``); for stacked-ranks grads at dispatch level use
    :meth:`reduce_stacked`, which dispatches one jitted bucket-sum at a
    time — async dispatch lets bucket *k*'s reduction execute while later
    buckets are still being packed (the EagerReducer overlap,
    ``collective/reducer.h:88``).
    """

    def __init__(self, axis: str = "dp", bucket_bytes: Optional[int] = None):
        self.axis = axis
        if bucket_bytes is None:
            bucket_bytes = int(flag("comm_overlap_bucket_mb")) << 20
        self.bucket_bytes = max(int(bucket_bytes), 1)
        self._jitted: Dict[Tuple, Any] = {}

    def bucketize(self, grads: Dict[str, jax.Array]) -> List[List[str]]:
        """Greedy size-bucketed partition of the grad names, preserving
        order; every bucket holds at least one parameter."""
        buckets: List[List[str]] = []
        cur: List[str] = []
        cur_bytes = 0
        for name, g in grads.items():
            nbytes = int(g.size) * jnp.dtype(g.dtype).itemsize
            if cur and cur_bytes + nbytes > self.bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(name)
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
        return buckets

    @staticmethod
    def _flatten(gs: List[jax.Array]) -> jax.Array:
        return jnp.concatenate([g.ravel() for g in gs])

    @staticmethod
    def _unflatten(flat: jax.Array, gs: List[jax.Array]) -> List[jax.Array]:
        out, off = [], 0
        for g in gs:
            out.append(lax.dynamic_slice_in_dim(
                flat, off, g.size, 0).reshape(g.shape))
            off += g.size
        return out

    def reduce_in_axis(self, grads: Dict[str, jax.Array],
                       op: str = "all_reduce") -> Dict[str, jax.Array]:
        """Bucketed reduce inside a shard_map/pmap context with
        ``self.axis`` bound. ``op``: ``all_reduce`` (``psum``, DP grads)
        or ``reduce_scatter`` (``psum_scatter`` over flat buckets,
        ZeRO-style — caller keeps the shard layout). One collective per
        bucket: bucket k's reduction overlaps the backward segments that
        still have to produce bucket k+1's grads.
        """
        out = dict(grads)
        for names in self.bucketize(grads):
            gs = [grads[n] for n in names]
            flat = self._flatten(gs)
            if op == "reduce_scatter":
                red = self._psum_scatter_gather(flat)
            else:
                red = lax.psum(flat, self.axis)
            for n, g in zip(names, self._unflatten(red, gs)):
                out[n] = g
        return out

    def _psum_scatter_gather(self, flat: jax.Array,
                             axis_size: Optional[int] = None) -> jax.Array:
        """``psum_scatter`` + ``all_gather`` of one flat bucket, padded:
        ``lax.psum_scatter(tiled=True)`` requires the bucket length to
        divide the axis size, but ``bucketize`` produces arbitrary
        lengths — pad with zeros to the next multiple, slice back after
        the gather. Values are bitwise-identical to a plain ``psum`` (the
        zero tail reduces separately and is dropped)."""
        if axis_size is None:
            axis_size = lax.psum(1, self.axis)
        n = int(axis_size)
        pad = (-int(flat.size)) % n
        if pad:
            padded = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        else:
            padded = flat
        red = lax.psum_scatter(padded, self.axis, tiled=True)
        red = lax.all_gather(red, self.axis, tiled=True)
        return red[:flat.size] if pad else red

    def reduce_stacked(self, grads: Dict[str, jax.Array],
                       mean: bool = False) -> Dict[str, jax.Array]:
        """Dispatch-level bucketed reduction of stacked-ranks grads
        (leaves ``[nranks, ...]`` — the eager hybrid-parallel form). One
        jitted sum per bucket, dispatched back-to-back: jax dispatch is
        async, so bucket k's reduction runs on device while bucket k+1 is
        still being packed on the host. Each bucket is a telemetry
        ``comm`` phase."""
        from ..observability import step_monitor
        tm = step_monitor.current()
        out = dict(grads)
        for names in self.bucketize(grads):
            gs = [grads[n] for n in names]
            sig = tuple((g.shape, str(g.dtype)) for g in gs) + (mean,)
            fn = self._jitted.get(sig)
            if fn is None:
                def _bucket_sum(gs, _mean=mean):
                    flat = jnp.concatenate(
                        [g.reshape(g.shape[0], -1) for g in gs], axis=1)
                    red = jnp.mean(flat, 0) if _mean else jnp.sum(flat, 0)
                    outs, off = [], 0
                    for g in gs:
                        size = 1
                        for d in g.shape[1:]:
                            size *= int(d)
                        outs.append(red[off:off + size].reshape(g.shape[1:]))
                        off += size
                    return outs
                fn = self._jitted[sig] = jax.jit(_bucket_sum)
            nbytes = sum(int(g.size) * jnp.dtype(g.dtype).itemsize
                         for g in gs)
            with tm.phase("comm", op="dp_bucket", bytes=nbytes,
                          params=len(names)):
                red = fn(gs)
            for n, g in zip(names, red):
                out[n] = g
        return out
