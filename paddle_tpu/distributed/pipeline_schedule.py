"""Compiled SPMD pipeline-parallel schedule.

Reference design: ``fleet/meta_parallel/pipeline_parallel.py:387``
(forward_backward_pipeline) — an imperative host loop issuing eager NCCL
send/recv per microbatch (1F1B), with ``PipelineParallelWithInterleave``
(:822) for virtual stages.

TPU-native design: the schedule is a *single compiled program*. The pipeline
trunk (homogeneous stages) runs inside ``jax.shard_map`` manual over the
``pp`` mesh axis (other axes stay GSPMD-auto, so TP/DP/FSDP compose
untouched): a ``lax.scan`` over ``n_micro + S - 1`` ticks where every tick
each device applies ITS stage's block to its current microbatch and
``ppermute``s the activation to the next stage over the ICI ring. Backward is
``jax.grad`` of the scan — XLA derives the reverse pipeline (the 1F1B
cooldown) automatically; per-stage ``jax.checkpoint`` gives the 1F1B
activation-memory profile (each in-flight microbatch saves only its stage
input). Bubble ticks compute on clipped dummy microbatches and contribute
zero gradient (standard for compiled pipelines).

Interleaved virtual stages (VPP, ref ``PipelineParallelWithInterleave``
:822): ``num_chunks=V`` partitions the trunk into S*V virtual stages laid
out Megatron-style (device s holds chunks {v*S+s}); the circular schedule
streams each microbatch V times around the ring, shrinking the bubble
fraction by V.

Heterogeneous head/tail layers (embedding before the trunk, final norm/head
after) run OUTSIDE the manual region under plain GSPMD, replicated over pp —
the idiom used by production TPU pipelining (praxis/MaxText), where only the
repeated-block trunk is pipelined. A PipelineLayer whose stages cannot be
made homogeneous pipelines through ``spmd_pipeline_het`` — per-stage
programs dispatched by ``lax.switch`` on the pp index over flat per-stage
param buffers — provided stage boundary activations share one shape/dtype
and no params are shared across stages; otherwise it falls back to a
non-pipelined microbatch-accumulation step (correct, not pp-scaled) with a
warning.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..framework.functional import functional_call
from ..nn.layer import Layer

__all__ = ["spmd_pipeline", "spmd_pipeline_het", "make_pipeline_train_step",
           "analyze_pipeline", "spmd_pipeline_serial", "build_serial_probe"]

PP_AXIS = "pp"


# ---------------------------------------------------------------------------
# Core engine: homogeneous-stage GPipe/1F1B scan over the pp axis.
# ---------------------------------------------------------------------------

def spmd_pipeline(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stacked_params: Any, x_mb: jax.Array, mesh,
                  pp_axis: str = PP_AXIS, remat: bool = True,
                  num_chunks: int = 1) -> jax.Array:
    """Run ``n_micro`` microbatches through ``S`` pipeline stages.

    stage_fn(stage_params, x) -> y with y.shape == x.shape.
    stacked_params: pytree whose leaves have a leading stage dim [S, ...]
    when ``num_chunks == 1``, or [S, V, ...] (device-major) when
    ``num_chunks == V > 1`` — device s, chunk v holds *virtual* stage
    ``v*S + s`` (Megatron VPP layer assignment,
    ref pipeline_parallel.py:822 PipelineParallelWithInterleave).
    x_mb: [n_micro, mb, ...] inputs (outputs of the pre-trunk layers).
    Returns y_mb [n_micro, mb, ...]: the last virtual stage's outputs,
    identical to sequentially applying virtual stages 0..S*V-1.

    Interleaved schedule (V > 1): the circular pipeline — device s
    processes (microbatch m, chunk v) at tick ``v*n + m + s``; activations
    ``ppermute`` around the pp ring every tick, and the ring wrap
    (device S-1, chunk v) -> (device 0, chunk v+1) is delayed ``n - S``
    ticks through a FIFO. Total ticks = n*V + S - 1, so the bubble
    fraction shrinks from (S-1)/(n+S-1) to (S-1)/(n*V+S-1) — the VPP
    bubble reduction, in one compiled scan (backward derived by autodiff).
    Requires n_micro >= S when V > 1.
    """
    S = mesh.shape[pp_axis]
    V = num_chunks
    n_micro = x_mb.shape[0]
    if V > 1 and n_micro < S:
        raise ValueError(
            f"interleaved pipeline needs n_micro >= pp degree "
            f"(got n_micro={n_micro}, pp={S})")
    total_ticks = n_micro * V + S - 1
    wrap_delay = n_micro - S  # ticks an activation waits before re-entry
    body = jax.checkpoint(stage_fn) if remat else stage_fn

    def fn(sp, xs):
        # Manual over pp: sp leaves arrive as [1, ...] (this stage's slice).
        sp_local = jax.tree_util.tree_map(lambda a: a[0], sp)
        stage = lax.axis_index(pp_axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def chunk_params(v):
            if V == 1:
                return sp_local
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
                sp_local)

        def tick(carry, t):
            recv, fifo, outbuf = carry
            j = jnp.clip(t - stage, 0, n_micro * V - 1)  # logical work index
            m = j % n_micro
            v = j // n_micro
            if V == 1:
                x0 = xs[m]
            else:
                # Chunk 0 consumes fresh microbatches; later chunks consume
                # the ring-wrapped activation. The wrap arrives n-S ticks
                # early and waits in a size-(n-S) ring buffer: slot t % w
                # holds the activation that arrived at tick t-w — exactly
                # the one (m, v) needs (read happens before this tick's
                # arrival overwrites the slot).
                delayed = recv if wrap_delay == 0 else fifo[t % wrap_delay]
                x0 = jnp.where(v == 0, xs[m], delayed)
            x_in = jnp.where(stage == 0, x0, recv)
            y = body(chunk_params(v), x_in)
            # The last device finishes microbatch m's last chunk at tick
            # (V-1)*n + m + S - 1.
            valid = jnp.logical_and(t - stage >= 0,
                                    t - stage < n_micro * V)
            collect = jnp.logical_and(
                valid, jnp.logical_and(stage == S - 1, v == V - 1))
            outbuf = jnp.where(
                collect, lax.dynamic_update_index_in_dim(outbuf, y, m, 0),
                outbuf)
            send = lax.ppermute(y, pp_axis, perm)
            if V > 1 and wrap_delay > 0:
                fifo = lax.dynamic_update_index_in_dim(
                    fifo, recv, t % wrap_delay, 0)
            return (send, fifo, outbuf), None

        # Carry values vary per pp rank — mark the invariant zeros as varying
        # so the scan carry types stay fixed.
        var = lambda a: lax.pcast(a, (pp_axis,), to="varying")
        fifo0 = jnp.zeros((max(wrap_delay, 1),) + xs.shape[1:], xs.dtype) \
            if V > 1 else jnp.zeros((1,) + xs.shape[1:], xs.dtype)
        init = (var(jnp.zeros_like(xs[0])), var(fifo0),
                var(jnp.zeros_like(xs)))
        (_, _, outbuf), _ = lax.scan(tick, init, jnp.arange(total_ticks))
        # Valid only on the last stage; replicate across pp so downstream
        # (GSPMD-auto) layers see a consistent value.
        outbuf = lax.psum(
            jnp.where(stage == S - 1, outbuf, jnp.zeros_like(outbuf)),
            pp_axis)
        return outbuf

    pspec = jax.tree_util.tree_map(lambda _: P(pp_axis), stacked_params)
    # check_vma=True is required for partial-manual shard_map (only the pp
    # axis is manual; dp/mp/… stay GSPMD-automatic so TP/FSDP compose).
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
        axis_names={pp_axis}, check_vma=True)(stacked_params, x_mb)


# ---------------------------------------------------------------------------
# Serial (one-device) schedule emulation: measure the pp machinery on a
# single chip (VERDICT r5 ask #3/#4 carry-over).
# ---------------------------------------------------------------------------

def spmd_pipeline_serial(stage_fn: Callable[[Any, jax.Array], jax.Array],
                         stacked_params: Any, x_mb: jax.Array,
                         n_stages: int, remat: bool = True) -> jax.Array:
    """The exact ``spmd_pipeline`` tick schedule with all ``S`` stages
    resident on ONE device: the per-tick ``ppermute`` ring hop becomes a
    stage-dim shift and the S per-device stage applications run as one
    ``vmap`` over the stage axis. Every tick executes the same work the
    real pp=S schedule executes per device — including the (S-1) bubble
    ticks' clipped dummy microbatches — so device-timing this against the
    plain (non-pipelined) microbatch loop isolates the schedule
    *machinery* cost: tick scan overhead, ring-buffer shifts, output
    masking, bubble compute. Semantically identical to sequentially
    applying stages 0..S-1 to each microbatch.

    x_mb: [n_micro, mb, ...]; stacked_params leaves [S, ...].
    Returns [n_micro, mb, ...] last-stage outputs.
    """
    S = n_stages
    n_micro = x_mb.shape[0]
    total_ticks = n_micro + S - 1
    body = jax.checkpoint(stage_fn) if remat else stage_fn
    vbody = jax.vmap(body)

    def tick(carry, t):
        ring, outbuf = carry  # ring[s]: stage s's output from last tick
        m_in = jnp.clip(t, 0, n_micro - 1)
        # stage 0 consumes the fresh microbatch; stage s consumes what
        # stage s-1 produced last tick (the ppermute hop, serialized)
        ins = jnp.concatenate([x_mb[m_in][None], ring[:-1]], axis=0)
        outs = vbody(stacked_params, ins)
        oidx = jnp.clip(t - (S - 1), 0, n_micro - 1)
        outbuf = jnp.where(
            t >= S - 1,
            lax.dynamic_update_index_in_dim(outbuf, outs[-1], oidx, 0),
            outbuf)
        return (outs, outbuf), None

    init = (jnp.zeros((S,) + x_mb.shape[1:], x_mb.dtype),
            jnp.zeros_like(x_mb))
    (_, outbuf), _ = lax.scan(tick, init, jnp.arange(total_ticks))
    return outbuf


def build_serial_probe(pl, n_stages: int, n_microbatch: int,
                       remat: bool = True):
    """Loss functions for the single-chip pp-machinery measurement.

    Returns ``(loss_sched, loss_plain, analysis)`` or None when the
    PipelineLayer has no homogeneous ``n_stages``-partitionable trunk.
    Both take ``(params, inputs, labels)`` over the full param dict and
    compute the identical model loss; ``loss_sched`` routes the trunk
    through :func:`spmd_pipeline_serial` (schedule machinery + bubble),
    ``loss_plain`` through a plain scan over microbatches (the
    no-machinery reference). Ideal sched/plain time ratio is
    ``(n_micro + S - 1) / n_micro`` (the bubble); anything above it is
    machinery overhead.
    """
    analysis = analyze_pipeline(pl, n_stages)
    if not analysis.homogeneous:
        return None

    first_prefix: Dict[int, str] = {}
    for i, (layer, _) in enumerate(pl._built):
        if isinstance(layer, Layer) and id(layer) not in first_prefix:
            first_prefix[id(layer)] = str(i)

    def prefix_of(layer, gidx):
        return first_prefix.get(id(layer), str(gidx))

    def stage_fn(stage_params, x):
        for j, layer, fwd in analysis.template:
            sub = _layer_params(stage_params, str(j))
            if fwd is not None:
                with _substituted(layer, sub):
                    x = fwd(layer, x)
            else:
                x = functional_call(layer, sub, x, training=True)
        return x

    def stacked(full_params):
        out: Dict[str, jax.Array] = {}
        for j, _, _ in analysis.template:
            core0_gidx, layer, _ = analysis.cores[0][j]
            rels = _layer_params(full_params, str(core0_gidx)).keys() \
                if isinstance(layer, Layer) else []
            for rel in rels:
                out[f"{j}.{rel}"] = jnp.stack(
                    [full_params[f"{core[j][0]}.{rel}"]
                     for core in analysis.cores])
        return out

    def _pre_mb(params, inputs):
        bsz = inputs.shape[0]
        mb = bsz // n_microbatch
        x = _apply_layers(analysis.pre, params, inputs, prefix_of, True)
        return x.reshape((n_microbatch, mb) + x.shape[1:]), bsz

    def _post_loss(params, y_mb, bsz, labels):
        y = y_mb.reshape((bsz,) + y_mb.shape[2:])
        out = _apply_layers(analysis.post, params, y, prefix_of, True)
        return jnp.mean(pl.loss_fn(out, labels))

    def loss_sched(params, inputs, labels):
        x_mb, bsz = _pre_mb(params, inputs)
        y_mb = spmd_pipeline_serial(stage_fn, stacked(params), x_mb,
                                    n_stages, remat=remat)
        return _post_loss(params, y_mb, bsz, labels)

    def loss_plain(params, inputs, labels):
        x_mb, bsz = _pre_mb(params, inputs)
        sp = stacked(params)
        body = jax.checkpoint(stage_fn) if remat else stage_fn

        def per_micro(_, x):
            for s in range(n_stages):
                x = body(jax.tree_util.tree_map(lambda a, s=s: a[s], sp), x)
            return None, x

        _, y_mb = lax.scan(per_micro, None, x_mb)
        return _post_loss(params, y_mb, bsz, labels)

    return loss_sched, loss_plain, analysis


# ---------------------------------------------------------------------------
# Heterogeneous-stage engine: lax.switch dispatch by stage index.
# ---------------------------------------------------------------------------

def _flatten_stage_params(per_stage: Sequence[Dict[str, jax.Array]]):
    """Pack S differently-structured stage param dicts into per-dtype
    [S, L] buffers (padded to the largest stage) + static unpack specs.

    This is what makes *non-homogeneous* stages compilable as one SPMD
    program: param structure differences disappear into flat buffers, and
    ``lax.switch`` picks the stage's unpack+apply branch at run time.
    """
    S = len(per_stage)
    dtypes = sorted({str(v.dtype) for sp in per_stage for v in sp.values()})
    specs = []   # per stage: {key: (shape, dtype, offset)}
    lens = {dt: 0 for dt in dtypes}
    for sp in per_stage:
        spec = {}
        off = {dt: 0 for dt in dtypes}
        for key in sorted(sp):
            v = sp[key]
            dt = str(v.dtype)
            spec[key] = (v.shape, v.dtype, off[dt])
            off[dt] += int(np.prod(v.shape)) if v.shape else 1
        specs.append(spec)
        for dt in dtypes:
            lens[dt] = max(lens[dt], off[dt])

    def pack(per_stage_now):
        bufs = {}
        for dt in dtypes:
            rows = []
            for s in range(S):
                parts = [per_stage_now[s][k].ravel()
                         for k in sorted(per_stage_now[s])
                         if str(per_stage_now[s][k].dtype) == dt]
                row = jnp.concatenate(parts) if parts else \
                    jnp.zeros((0,), jnp.dtype(dt))
                pad = lens[dt] - row.shape[0]
                if pad:
                    row = jnp.concatenate(
                        [row, jnp.zeros((pad,), jnp.dtype(dt))])
                rows.append(row)
            bufs[dt] = jnp.stack(rows)
        return bufs

    def unpack(bufs_row, stage: int) -> Dict[str, jax.Array]:
        out = {}
        for key, (shape, dtype, off) in specs[stage].items():
            n = int(np.prod(shape)) if shape else 1
            flat = lax.slice_in_dim(bufs_row[str(dtype)], off, off + n, axis=0)
            out[key] = flat.reshape(shape)
        return out

    return pack, unpack


def spmd_pipeline_het(stage_fns: Sequence[Callable[[Any, jax.Array], jax.Array]],
                      bufs: Dict[str, jax.Array], unpack,
                      x_first: jax.Array, x_mb_shape, mesh,
                      pp_axis: str = PP_AXIS, remat: bool = True):
    """Pipeline with a *different* computation per stage.

    stage_fns[s](params_s, x) -> y; stage 0 consumes entries of ``x_first``
    ([n_micro, mb, ...] raw inputs, any dtype), stages 1..S-1 consume the
    ring activation (shape/dtype ``x_mb_shape``, which every stage's output
    must match). Dispatch is ``lax.switch`` on the device's pp index over
    branches that unpack their own slice of the flat param buffers — the
    TPU-native answer to the reference's per-rank heterogeneous stage
    programs (pipeline_parallel.py builds a different sub-model per rank).
    """
    S = mesh.shape[pp_axis]
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for pp={S}")
    n_micro = x_first.shape[0]
    total_ticks = n_micro + S - 1

    def fn(bufs_sh, xs):
        local = {dt: a[0] for dt, a in bufs_sh.items()}
        stage = lax.axis_index(pp_axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def make_branch(s):
            def branch(x_ring, x_raw):
                params = unpack(local, s)
                x = x_raw if s == 0 else x_ring
                return stage_fns[s](params, x)
            return jax.checkpoint(branch) if remat else branch

        branches = [make_branch(s) for s in range(S)]

        def tick(carry, t):
            recv, outbuf = carry
            m = jnp.clip(t - stage, 0, n_micro - 1)
            y = lax.switch(stage, branches, recv, xs[m])
            collect = jnp.logical_and(t >= S - 1, stage == S - 1)
            oidx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            outbuf = jnp.where(
                collect, lax.dynamic_update_index_in_dim(outbuf, y, oidx, 0),
                outbuf)
            send = lax.ppermute(y, pp_axis, perm)
            return (send, outbuf), None

        var = lambda a: lax.pcast(a, (pp_axis,), to="varying")
        ring0 = jnp.zeros(x_mb_shape.shape, x_mb_shape.dtype)
        init = (var(ring0),
                var(jnp.zeros((n_micro,) + tuple(x_mb_shape.shape),
                              x_mb_shape.dtype)))
        (_, outbuf), _ = lax.scan(tick, init, jnp.arange(total_ticks))
        outbuf = lax.psum(
            jnp.where(stage == S - 1, outbuf, jnp.zeros_like(outbuf)),
            pp_axis)
        return outbuf

    pspec = {dt: P(pp_axis) for dt in bufs}
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(pspec, P()), out_specs=P(),
        axis_names={pp_axis}, check_vma=True)(bufs, x_first)


# ---------------------------------------------------------------------------
# PipelineLayer analysis: pre / homogeneous core / post split.
# ---------------------------------------------------------------------------

class PipelineAnalysis:
    def __init__(self, pre, cores, post, template, homogeneous):
        self.pre = pre            # [(global_idx, layer, fwd)]
        self.cores = cores        # per stage: [(global_idx, layer, fwd)]
        self.post = post
        self.template = template  # stage-0 core [(local_j, layer, fwd)]
        self.homogeneous = homogeneous


def _param_struct(layer: Layer):
    return tuple(sorted((name, tuple(ref.shape), str(ref.dtype))
                        for name, ref in layer.named_parameters()))


def analyze_pipeline(pl, n_stages: int) -> PipelineAnalysis:
    """Find the pipelineable trunk: the longest contiguous run of
    identically-structured layers (same class + param shapes — the repeated
    transformer block), trimmed to a multiple of n_stages. Everything before
    runs as 'pre', everything after as 'post' (both outside the manual
    pipeline region, GSPMD-replicated over pp — praxis/MaxText-style, only
    the repeated trunk is pipelined). Tied/shared layers are never
    pipelined."""
    built = pl._built
    shared_ids = {id(l) for l in pl.shared_layers().values()}

    def sig_of(entry):
        layer, _ = entry
        if not isinstance(layer, Layer) or id(layer) in shared_ids:
            return None
        return (type(layer).__name__, _param_struct(layer))

    sigs = [sig_of(e) for e in built]
    best = (0, 0)  # (start, length) of the longest equal-signature run
    i = 0
    while i < len(sigs):
        if sigs[i] is None:
            i += 1
            continue
        j = i
        while j < len(sigs) and sigs[j] == sigs[i]:
            j += 1
        if j - i > best[1]:
            best = (i, j - i)
        i = j
    start, length = best
    per_stage = length // n_stages if n_stages > 0 else 0
    if n_stages <= 1 or per_stage < 1:
        return PipelineAnalysis([(i, *built[i]) for i in range(len(built))],
                                [], [], [], False)
    trunk_len = per_stage * n_stages
    # Run-length remainder stays in 'pre' (only full multiples of n_stages
    # rotate through the stage ring).
    t0 = start + (length - trunk_len)
    pre = [(i, *built[i]) for i in range(t0)]
    post = [(i, *built[i]) for i in range(t0 + trunk_len, len(built))]
    cores = [[(t0 + s * per_stage + j, *built[t0 + s * per_stage + j])
              for j in range(per_stage)] for s in range(n_stages)]
    template = [(j, l, f) for j, (_, l, f) in enumerate(cores[0])]
    return PipelineAnalysis(pre, cores, post, template, True)


def _layer_params(full: Dict[str, jax.Array], prefix: str) -> Dict[str, jax.Array]:
    plen = len(prefix) + 1
    return {k[plen:]: v for k, v in full.items() if k.startswith(prefix + ".")}


def _apply_layers(layers, full_params, x, prefix_of, training: bool):
    """Run [(global_idx, layer, fwd)] sequentially with substituted params."""
    for gidx, layer, fwd in layers:
        if isinstance(layer, Layer):
            sub = _layer_params(full_params, prefix_of(layer, gidx))
            if fwd is not None:
                with _substituted(layer, sub):
                    x = fwd(layer, x)
            else:
                x = functional_call(layer, sub, x, training=training)
        else:
            x = fwd(layer, x) if fwd is not None else layer(x)
    return x


import contextlib


@contextlib.contextmanager
def _substituted(layer: Layer, params: Dict[str, jax.Array]):
    from ..framework.functional import _swapped_state
    with _swapped_state(layer, params, None):
        yield


def _try_het_pipeline(pl, S: int, prefix_of):
    """Build switch-dispatch pipeline pieces for a non-homogeneous layer
    sequence: S per-stage apply fns + per-stage (gidx, rel) param key specs.
    Returns None when not applicable: shared/tied layers need cross-stage
    grad reduction the flat-buffer path doesn't do, and each stage must own
    at least one layer."""
    if pl.shared_layers():
        return None
    n = len(pl._built)
    if n < S:
        return None
    bounds = [int(round(s * n / S)) for s in range(S)] + [n]
    groups = [[(i, *pl._built[i]) for i in range(bounds[s], bounds[s + 1])]
              for s in range(S)]
    if any(not g for g in groups):
        return None

    pack_specs = []
    for g in groups:
        spec = []
        for gidx, layer, _ in g:
            if isinstance(layer, Layer):
                spec.extend((gidx, rel)
                            for rel, _ in layer.named_parameters())
        pack_specs.append(spec)

    def make_stage_fn(g):
        def stage_fn(params, x):
            return _apply_layers(g, params, x, prefix_of, True)
        return stage_fn

    return [make_stage_fn(g) for g in groups], pack_specs


def _ring_probe(stage_fns, per_stage, x_mb):
    """Abstract-eval each stage; returns the list of per-stage output
    ShapeDtypeStructs (stage s fed stage s-1's output; stage 0 fed one
    microbatch)."""
    x = jax.ShapeDtypeStruct(tuple(x_mb.shape[1:]), x_mb.dtype)
    shapes = []
    for s, fn in enumerate(stage_fns):
        x = jax.eval_shape(fn, per_stage[s], x)
        shapes.append(x)
    return shapes


# ---------------------------------------------------------------------------
# Train step factory (used by fleet PipelineParallel.train_batch).
# ---------------------------------------------------------------------------

def make_pipeline_train_step(pl, opt, hcg=None, n_microbatch: int = 1,
                             schedule: str = "1F1B"):
    """Build step(params, opt_state, inputs, labels, lr) ->
    (new_params, new_opt_state, mean_loss) running the pipeline schedule."""
    from .topology import get_hybrid_mesh
    import warnings
    mesh = hcg.mesh if hcg is not None and hasattr(hcg, "mesh") \
        else get_hybrid_mesh()
    S = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
    # Virtual stages (VPP): the trunk is partitioned into S*V virtual
    # stages; device s holds chunks {v*S+s} and the interleaved schedule
    # cuts the bubble by V (ref PipelineParallelWithInterleave :822/:1016).
    V = 1
    if S > 1 and pl.total_stages > S:
        if pl.total_stages % S == 0 and n_microbatch >= S:
            V = pl.total_stages // S
        else:
            warnings.warn(
                f"PipelineLayer requested total_stages={pl.total_stages} "
                f"but mesh pp={S} (needs total_stages % pp == 0 and "
                f"n_microbatch >= pp for interleaving); running the correct "
                f"{S}-stage schedule without interleaving.")
    analysis = analyze_pipeline(pl, S * V) if S > 1 else None
    if analysis is not None and not analysis.homogeneous and V > 1:
        V = 1  # heterogeneous trunks pipeline un-interleaved
        analysis = analyze_pipeline(pl, S)
    remat = schedule.upper() != "FTHENB" or pl.recompute_interval > 0

    # Map shared layer objects to their registered prefix (first position).
    first_prefix: Dict[int, str] = {}
    for i, (layer, _) in enumerate(pl._built):
        if isinstance(layer, Layer) and id(layer) not in first_prefix:
            first_prefix[id(layer)] = str(i)

    def prefix_of(layer, gidx):
        return first_prefix.get(id(layer), str(gidx))

    use_pipeline = (S > 1 and analysis is not None and analysis.homogeneous
                    and n_microbatch >= 1)
    het = None
    if S > 1 and analysis is not None and not analysis.homogeneous:
        het = _try_het_pipeline(pl, S, prefix_of)
        if het is None:
            warnings.warn(
                "PipelineLayer stages are non-homogeneous and not "
                "switch-pipelineable (shared layers or mismatched "
                "inter-stage activation shapes); falling back to the "
                "non-pipelined microbatch-accumulation step (correct, "
                "not pp-scaled).")

    def _stage_fn(stage_params, x):
        # stage_params: {f"{j}.{rel}": arr} for this stage's core layers.
        for j, layer, fwd in analysis.template:
            sub = _layer_params(stage_params, str(j))
            if fwd is not None:
                with _substituted(layer, sub):
                    x = fwd(layer, x)
            else:
                x = functional_call(layer, sub, x, training=True)
        return x

    def _stacked(full_params):
        """[S, ...] leaves for V == 1, [S, V, ...] (device-major) else."""
        out: Dict[str, jax.Array] = {}
        for j, _, _ in analysis.template:
            core0_gidx, layer, _ = analysis.cores[0][j]
            rels = _layer_params(full_params, str(core0_gidx)).keys() \
                if isinstance(layer, Layer) else []
            for rel in rels:
                if V == 1:
                    leaves = [full_params[f"{core[j][0]}.{rel}"]
                              for core in analysis.cores]
                    out[f"{j}.{rel}"] = jnp.stack(leaves)
                else:
                    rows = [jnp.stack(
                        [full_params[f"{analysis.cores[v * S + s][j][0]}.{rel}"]
                         for v in range(V)]) for s in range(S)]
                    out[f"{j}.{rel}"] = jnp.stack(rows)
        return out

    def loss_pipe(params, inputs, labels):
        bsz = inputs.shape[0]
        mb = bsz // n_microbatch
        x = _apply_layers(analysis.pre, params, inputs, prefix_of, True)
        x_mb = x.reshape((n_microbatch, mb) + x.shape[1:])
        stacked = _stacked(params)
        y_mb = spmd_pipeline(_stage_fn, stacked, x_mb, mesh,
                             remat=remat, num_chunks=V)
        y = y_mb.reshape((bsz,) + y_mb.shape[2:])
        out = _apply_layers(analysis.post, params, y, prefix_of, True)
        return jnp.mean(pl.loss_fn(out, labels))

    def loss_het(params, inputs, labels):
        bsz = inputs.shape[0]
        mb = bsz // n_microbatch
        x_mb = inputs.reshape((n_microbatch, mb) + inputs.shape[1:])
        stage_fns, pack_specs = het
        per_stage = [{f"{gidx}.{rel}": params[f"{gidx}.{rel}"]
                      for gidx, rel in spec} for spec in pack_specs]
        pack, unpack = _flatten_stage_params(per_stage)
        bufs = pack(per_stage)
        ring = _ring_probe(stage_fns, per_stage, x_mb)[0]
        y_mb = spmd_pipeline_het(stage_fns, bufs, unpack, x_mb, ring, mesh,
                                 remat=remat)
        out = y_mb.reshape((bsz,) + y_mb.shape[2:])
        return jnp.mean(pl.loss_fn(out, labels))

    def loss_fallback(params, inputs, labels):
        # Full model under GSPMD (no pp scaling), still microbatch-correct
        # since loss is a mean.
        out = inputs
        for i, (layer, fwd) in enumerate(pl._built):
            if isinstance(layer, Layer):
                sub = _layer_params(params, prefix_of(layer, i))
                if fwd is not None:
                    with _substituted(layer, sub):
                        out = fwd(layer, out)
                else:
                    out = functional_call(layer, sub, out, training=True)
            else:
                out = fwd(layer, out) if fwd is not None else layer(out)
        return jnp.mean(pl.loss_fn(out, labels))

    def make_step(loss_of):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def _step(params, opt_state, inputs, labels, lr):
            loss, grads = jax.value_and_grad(loss_of)(params, inputs, labels)
            new_params, new_state = opt.apply_gradients(params, grads,
                                                        opt_state, lr)
            return new_params, new_state, loss
        # Telemetry: dispatches are fingerprinted through the recompile
        # sentinel and timed as compile/device phases; .lower passes
        # through, so compiled-cost introspection still reaches the
        # executable.
        from ..observability.step_monitor import instrument_jitted
        return instrument_jitted(
            _step, name=f"pipeline_train_step:{loss_of.__name__}",
            donate=(0, 1))

    if use_pipeline:
        return make_step(loss_pipe)
    if het is None:
        return make_step(loss_fallback)

    # Heterogeneous candidate: the ring requires every stage output to share
    # one shape/dtype — only checkable once input shapes are known, so the
    # het-vs-fallback choice happens on first call (executor-cache idiom).
    cache: Dict[str, Any] = {}

    def step(params, opt_state, inputs, labels, lr):
        if "fn" not in cache:
            stage_fns, pack_specs = het
            per_stage = [{f"{gidx}.{rel}": params[f"{gidx}.{rel}"]
                          for gidx, rel in spec} for spec in pack_specs]
            mb = inputs.shape[0] // n_microbatch
            x_mb = jax.ShapeDtypeStruct(
                (n_microbatch, mb) + tuple(inputs.shape[1:]), inputs.dtype)
            shapes = _ring_probe(stage_fns, per_stage, x_mb)
            if len({(tuple(r.shape), str(r.dtype)) for r in shapes}) == 1:
                cache["fn"] = make_step(loss_het)
            else:
                warnings.warn(
                    f"non-homogeneous PipelineLayer stage outputs differ "
                    f"({[(tuple(r.shape), str(r.dtype)) for r in shapes]}); "
                    "falling back to the non-pipelined microbatch-"
                    "accumulation step (correct, not pp-scaled).")
                cache["fn"] = make_step(loss_fallback)
        return cache["fn"](params, opt_state, inputs, labels, lr)

    return step
