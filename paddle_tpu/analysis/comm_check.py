"""Static ICI accounting for decomposed-collective pipelines.

``pallas_check`` turns Mosaic's opaque compile-time kernel limits into
pure-arithmetic diagnostics; this module does the same for the
communication-overlap tier (``distributed/overlap.py``): every decomposed
ppermute loop declares a :class:`CommSpec` (hop count × bytes per hop vs
the volume of the single collective it replaces, and per-hop transfer
time vs the compute meant to hide it), checked on any host with no TPU
attached.

Checked per :class:`CommSpec`:
  C001  decomposed volume exceeds the one-shot collective's ring volume
        by more than the tolerance — the rewrite must overlap, never
        re-send (a mis-scheduled ring re-transfers chunks)      [error]
  C002  per-hop payload under the ICI latency floor — hop setup time
        dominates and the pipeline is slower than the fused
        collective regardless of overlap                        [warning]
  C003  per-hop link transfer time exceeds the hop's matmul compute —
        the transfer cannot hide under compute at these shapes  [warning]
  C004  a ``dcn``-class collective moves more than the post-reduce-
        scatter 1/ici_size shard of the bucket it reduces — the naive
        flat-allreduce-over-DCN blowup the hierarchical reduction
        (``distributed/multislice``) exists to avoid             [error]
  C005  per-hop DCN payload under the DCN latency floor — the
        cross-slice RTT dominates the wire time at this bucket
        size; grow FLAGS_multislice_dcn_bucket_mb               [warning]

**Link classes.** Every spec carries a ``link`` class: ``ici`` (the
within-slice torus, ~45 GB/s per direction) or ``dcn`` (the between-slice
data-center network, ~6 GB/s per chip and orders of magnitude more
latency). Mesh axes are classified by name through the :func:`dcn_axes`
registry (``slice`` by default; ``SliceTopology`` registers its axis) —
the same registry the jaxpr linter's J015 rule consults to flag
collectives that cross a DCN-class axis inside a scan/decode inner loop.

``enforce`` routes through :func:`jaxpr_lint.emit` under
``FLAGS_static_analysis``, like the Pallas checker's kernel-entry hook —
and it *records*: every spec it sees is appended, keyed by call site, to
any active :func:`recording` context, so the step-plan verifier
(:mod:`.plan_check`) can cross-check declared hop plans against the
collectives that actually traced (rules S001/S002).

Assumed v5e figures (SCALING.md): ~45 GB/s per ICI link direction,
197 bf16 TFLOP/s per chip.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import FrozenSet, Iterator, List, Tuple

from ..core.chip import chip_peaks
from .jaxpr_lint import Diagnostic, ERROR, WARNING, emit

__all__ = ["CommSpec", "check_comm_spec", "enforce", "record", "recording",
           "spec_for_allgather_matmul", "spec_for_matmul_reduce_scatter",
           "spec_for_cp_ring", "spec_for_slice_reduce_scatter",
           "spec_for_dcn_allreduce", "spec_for_slice_all_gather",
           "dcn_axes", "register_dcn_axis", "link_class",
           "ICI_GBPS", "DCN_GBPS", "PEAK_TFLOPS",
           "HOP_LATENCY_FLOOR_BYTES", "DCN_HOP_LATENCY_FLOOR_BYTES",
           "ALLGATHER_MATMUL", "MATMUL_REDUCE_SCATTER", "CP_RING",
           "SLICE_REDUCE_SCATTER", "DCN_ALLREDUCE", "SLICE_ALL_GATHER",
           "FLAT_ICI_ALLREDUCE", "SPEC_NAMES"]

# Canonical CommSpec names. Each factory below mints exactly one of
# these; the subsystems that register a spec re-export the subset they
# own (``distributed.overlap.SP_COMM_SPECS``,
# ``distributed.multislice.reducer.MULTISLICE_COMM_SPECS``,
# ``CP_RING`` for the ring-CP attention tier) and the step-pipeline
# pass contracts consume those exports — so a factory, its registering
# subsystem, and the G003 trace-ownership check can never drift on a
# name.
ALLGATHER_MATMUL = "allgather_matmul"
MATMUL_REDUCE_SCATTER = "matmul_reduce_scatter"
CP_RING = "cp_ring"
SLICE_REDUCE_SCATTER = "slice_reduce_scatter"
DCN_ALLREDUCE = "dcn_allreduce"
SLICE_ALL_GATHER = "slice_all_gather"
# Minted by the flat multislice baseline (``reducer._bucket_specs``),
# not by a factory here — the A/B arm C004 is meant to fire on.
FLAT_ICI_ALLREDUCE = "flat_ici_allreduce"
SPEC_NAMES = (ALLGATHER_MATMUL, MATMUL_REDUCE_SCATTER, CP_RING,
              SLICE_REDUCE_SCATTER, DCN_ALLREDUCE, SLICE_ALL_GATHER,
              FLAT_ICI_ALLREDUCE)

# Per-direction, per-link ICI bandwidth (v5e 2D torus) and bf16 peak. The
# accounting is static (it runs at trace time, with no chip attached), so
# it names the chip it plans for; the peak comes from the one table.
ICI_GBPS = 45.0
PEAK_TFLOPS = chip_peaks("TPU v5 lite").bf16_tflops

# Per-chip DCN bandwidth between pod slices (host NICs shared across the
# slice's chips; assumed v5e-class figure — ~7x below one ICI direction).
DCN_GBPS = 6.25

# Below this per-hop payload the ~1us collective-permute setup latency
# dominates the wire time (45 GB/s * 1us ≈ 45 KB); decomposing into such
# hops loses to the fused collective even with perfect overlap.
HOP_LATENCY_FLOOR_BYTES = 64 * 1024

# DCN analog: cross-slice RTT is tens of microseconds through the data
# center fabric (~40us x 6.25 GB/s ≈ 256 KB) — a DCN allreduce on buckets
# under this is latency-bound; FLAGS_multislice_dcn_bucket_mb sizes the
# hierarchical reducer's buckets well above it.
DCN_HOP_LATENCY_FLOOR_BYTES = 256 * 1024

# Decomposed volume may exceed the ring collective's by at most this
# factor (slack for the odd-n asymmetric direction split).
VOLUME_TOLERANCE = 1.25


# ---------------------------------------------------------------------------
# Mesh-axis link classes
# ---------------------------------------------------------------------------

# Axis names whose collectives cross the between-slice DCN rather than
# the within-slice ICI torus. "slice" is the canonical multi-slice axis
# (distributed/multislice.SliceTopology registers custom names here).
_DCN_AXES = {"slice"}


def dcn_axes() -> FrozenSet[str]:
    """Mesh axis names currently classified as DCN-class links."""
    return frozenset(_DCN_AXES)


def register_dcn_axis(name: str) -> None:
    """Classify a mesh axis name as a DCN-class link (consumed by the
    C004/C005 budgets and the jaxpr linter's J015 inner-loop rule)."""
    _DCN_AXES.add(str(name))


def link_class(axis: str) -> str:
    """"dcn" for registered DCN-class axes, else "ici"."""
    return "dcn" if axis in _DCN_AXES else "ici"


@dataclass
class CommSpec:
    """Declared hop plan of one decomposed-collective call site."""

    name: str
    axis_size: int
    hops: int              # total chunk transfers across both directions
    bytes_per_hop: int     # payload of ONE hop on ONE link direction
    collective_bytes: int  # per-rank volume of the ring collective replaced
    flops_per_hop: int     # matmul work hiding ONE direction's hop
    chunks: int = 1        # sub-chunk count per hop matmul
    directions: int = 2    # concurrent ring directions (bidirectional ICI)
    axis: str = "mp"       # mesh axis the decomposed loop permutes over
    link: str = "ici"      # link class the axis rides: "ici" | "dcn"
    # Hierarchical-reduction accounting (distributed/multislice): the full
    # pre-reduction bucket this stage's payload derives from, and the
    # intra-slice reduce-scatter degree available upstream of it. A
    # dcn-class stage whose payload is not the 1/ici_size shard of
    # reduced_from_bytes is the flat-over-DCN blowup C004 catches.
    reduced_from_bytes: int = 0
    ici_size: int = 1
    # One-direction per-rank payload crossing the link per step.
    payload_bytes: int = 0

    @property
    def decomposed_bytes(self) -> int:
        return self.hops * self.bytes_per_hop


def spec_for_allgather_matmul(b: int, s_local: int, k: int, m_local: int,
                              n: int, itemsize: int,
                              chunks: int = 1, axis: str = "mp") -> CommSpec:
    """AG->matmul: n-1 chunk transfers of the [B, s_local, K] activation
    chunk; each hop hides under one chunk x w_local matmul."""
    chunk_bytes = b * s_local * k * itemsize
    return CommSpec(
        name=ALLGATHER_MATMUL, axis_size=n, hops=max(n - 1, 0),
        bytes_per_hop=chunk_bytes,
        collective_bytes=max(n - 1, 0) * chunk_bytes,
        flops_per_hop=2 * b * s_local * k * m_local,
        chunks=chunks, axis=axis)


def spec_for_matmul_reduce_scatter(b: int, s_chunk: int, k_local: int,
                                   m: int, n: int, itemsize: int,
                                   chunks: int = 1, axis: str = "mp"
                                   ) -> CommSpec:
    """matmul->RS: two accumulators of HALF the [B, s_chunk, M] output
    chunk travel n-1 hops each; each hop hides under one
    chunk x w_half partial matmul."""
    half_bytes = b * s_chunk * max(m // 2, 1) * itemsize
    hops = 2 * max(n - 1, 0) if m >= 2 else max(n - 1, 0)
    return CommSpec(
        name=MATMUL_REDUCE_SCATTER, axis_size=n, hops=hops,
        bytes_per_hop=half_bytes,
        collective_bytes=max(n - 1, 0) * b * s_chunk * m * itemsize,
        flops_per_hop=2 * b * s_chunk * k_local * max(m // 2, 1),
        chunks=chunks, axis=axis)


def spec_for_cp_ring(b: int, s_local: int, heads: int, head_dim: int,
                     n: int, itemsize: int, axis: str = "sep") -> CommSpec:
    """Ring-attention CP hop plan: each of the n-1 hops moves one rank's
    [B, H, s_local, D] K and V chunks one step around the single-direction
    ring while the local Q block attends to the chunk that just arrived
    (QK^T + PV compute hides the transfer). The collective replaced is the
    KV all-gather a non-ring CP would issue — same per-rank volume."""
    kv_bytes = 2 * b * heads * s_local * head_dim * itemsize
    return CommSpec(
        name=CP_RING, axis_size=n, hops=max(n - 1, 0),
        bytes_per_hop=kv_bytes,
        collective_bytes=max(n - 1, 0) * kv_bytes,
        flops_per_hop=4 * b * heads * s_local * s_local * head_dim,
        directions=1, axis=axis)


# ---------------------------------------------------------------------------
# Hierarchical (multi-slice) reduction stages
# ---------------------------------------------------------------------------

def spec_for_slice_reduce_scatter(bucket_bytes: int, ici_size: int,
                                  axis: str = "dp") -> CommSpec:
    """Stage 1 of the hierarchical DP reduction: the intra-slice ring
    reduce-scatter of one flat grad bucket over the ICI data axis. Each
    rank moves (n-1)/n of the bucket and ends owning a fully-reduced
    1/n shard."""
    n = max(ici_size, 1)
    shard = -(-bucket_bytes // n)  # ceil: the padded shard
    return CommSpec(
        name=SLICE_REDUCE_SCATTER, axis_size=n, hops=max(n - 1, 0),
        bytes_per_hop=shard, collective_bytes=max(n - 1, 0) * shard,
        flops_per_hop=0, directions=1, axis=axis, link=link_class(axis),
        reduced_from_bytes=bucket_bytes, ici_size=n,
        payload_bytes=max(n - 1, 0) * shard)


def spec_for_dcn_allreduce(shard_bytes: int, dcn_size: int,
                           reduced_from_bytes: int, ici_size: int,
                           axis: str = "slice") -> CommSpec:
    """Stage 2: the inter-slice ring allreduce of the (already intra-slice
    reduced) shard over the DCN axis. ``shard_bytes`` is what actually
    crosses DCN per rank per direction — for the hierarchical plan it is
    ``reduced_from_bytes / ici_size``; the naive flat plan puts the whole
    bucket here and C004 fires."""
    n = max(dcn_size, 1)
    return CommSpec(
        name=DCN_ALLREDUCE, axis_size=n, hops=2 * max(n - 1, 0),
        bytes_per_hop=-(-shard_bytes // n) if n > 1 else shard_bytes,
        collective_bytes=2 * max(n - 1, 0) * (-(-shard_bytes // n)),
        flops_per_hop=0, directions=1, axis=axis, link=link_class(axis),
        reduced_from_bytes=reduced_from_bytes, ici_size=max(ici_size, 1),
        payload_bytes=shard_bytes)


def spec_for_slice_all_gather(bucket_bytes: int, ici_size: int,
                              axis: str = "dp") -> CommSpec:
    """Stage 3: the intra-slice all-gather rebuilding the full reduced
    bucket from the DCN-reduced shards — the reduce-scatter's mirror."""
    n = max(ici_size, 1)
    shard = -(-bucket_bytes // n)
    return CommSpec(
        name=SLICE_ALL_GATHER, axis_size=n, hops=max(n - 1, 0),
        bytes_per_hop=shard, collective_bytes=max(n - 1, 0) * shard,
        flops_per_hop=0, directions=1, axis=axis, link=link_class(axis),
        reduced_from_bytes=bucket_bytes, ici_size=n,
        payload_bytes=max(n - 1, 0) * shard)


def check_comm_spec(spec: CommSpec) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    where = f"comm:{spec.name}"
    if spec.axis_size <= 1 or spec.hops == 0:
        return diags
    if spec.collective_bytes and \
            spec.decomposed_bytes > VOLUME_TOLERANCE * spec.collective_bytes:
        diags.append(Diagnostic(
            rule="C001", name="decomposed-volume-blowup", severity=ERROR,
            message=(f"{spec.hops} hops x {spec.bytes_per_hop / 2**20:.2f}"
                     f" MiB = {spec.decomposed_bytes / 2**20:.2f} MiB moved"
                     f" vs {spec.collective_bytes / 2**20:.2f} MiB for the"
                     " ring collective — the decomposition re-sends chunks"),
            where=where,
            hint="the hop schedule must deliver each chunk exactly once "
                 "per link direction (check the permutation tables)"))
    if spec.link == "ici" and spec.bytes_per_hop < HOP_LATENCY_FLOOR_BYTES:
        diags.append(Diagnostic(
            rule="C002", name="hop-below-latency-floor", severity=WARNING,
            message=(f"per-hop payload {spec.bytes_per_hop / 1024:.1f} KiB"
                     f" is under the {HOP_LATENCY_FLOOR_BYTES // 1024} KiB"
                     " latency floor — hop setup dominates and the fused"
                     " collective wins regardless of overlap"),
            where=where,
            hint="decompose only at production shapes, or lower the chunk "
                 "count; FLAGS_comm_overlap=off for this layer size"))
    # One pipeline step moves bytes_per_hop on EACH link direction
    # concurrently while `directions` hop-matmuls execute: the transfer
    # that must hide is one link's, the compute hiding it is all of it.
    link_gbps = DCN_GBPS if spec.link == "dcn" else ICI_GBPS
    hop_transfer_s = spec.bytes_per_hop / (link_gbps * 1e9)
    hop_compute_s = (spec.directions * spec.flops_per_hop /
                     (PEAK_TFLOPS * 1e12))
    if hop_compute_s > 0 and hop_transfer_s > hop_compute_s:
        diags.append(Diagnostic(
            rule="C003", name="hop-transfer-exceeds-compute",
            severity=WARNING,
            message=(f"one hop moves {spec.bytes_per_hop / 2**20:.2f} MiB"
                     f" (~{hop_transfer_s * 1e6:.1f} us on"
                     f" {link_gbps:.0f} GB/s {spec.link.upper()}) but the"
                     f" concurrent hop matmuls total only"
                     f" {spec.directions * spec.flops_per_hop / 1e9:.2f}"
                     f" GFLOP (~{hop_compute_s * 1e6:.1f} us at"
                     f" {PEAK_TFLOPS:.0f} TFLOP/s) — the transfer cannot"
                     " hide under compute"),
            where=where,
            hint="the layer is bandwidth-bound at this shape; expect the "
                 "decomposition to tie, not win — confirm on the device "
                 "A/B before enabling"))
    if spec.link == "dcn" and spec.reduced_from_bytes > 0 \
            and spec.ici_size > 1:
        shard = -(-spec.reduced_from_bytes // spec.ici_size)
        if spec.payload_bytes > VOLUME_TOLERANCE * shard:
            diags.append(Diagnostic(
                rule="C004", name="dcn-volume-blowup", severity=ERROR,
                message=(f"{spec.payload_bytes / 2**20:.2f} MiB of a"
                         f" {spec.reduced_from_bytes / 2**20:.2f} MiB"
                         f" bucket crosses DCN per rank, but an intra-slice"
                         f" reduce-scatter over {spec.ici_size} ICI ranks"
                         f" would shrink the DCN payload to the"
                         f" {shard / 2**20:.2f} MiB shard — the flat"
                         " allreduce-over-DCN plan re-sends the whole"
                         " bucket across the slow link"),
                where=where,
                hint="reduce hierarchically: intra-slice reduce-scatter ->"
                     " DCN allreduce on the 1/ici shard -> intra-slice"
                     " all-gather (distributed/multislice."
                     "HierarchicalGradReducer, FLAGS_multislice="
                     "hierarchical)"))
    if spec.link == "dcn" and \
            spec.bytes_per_hop < DCN_HOP_LATENCY_FLOOR_BYTES:
        diags.append(Diagnostic(
            rule="C005", name="dcn-hop-below-latency-floor",
            severity=WARNING,
            message=(f"per-hop DCN payload {spec.bytes_per_hop / 1024:.1f}"
                     f" KiB is under the"
                     f" {DCN_HOP_LATENCY_FLOOR_BYTES // 1024} KiB DCN"
                     " latency floor — the cross-slice RTT dominates the"
                     " wire time at this bucket size"),
            where=where,
            hint="grow the DCN bucket "
                 "(FLAGS_multislice_dcn_bucket_mb) so fewer, larger "
                 "buckets amortize the per-collective DCN latency"))
    return diags


# ---------------------------------------------------------------------------
# Per-trace registry: declared specs, keyed by call site
# ---------------------------------------------------------------------------

# Stack of active recorder lists. The step-plan verifier opens a
# recording around one step trace; every enforce() fired by a decomposed
# call site during that trace lands in it, so the declared hop plans and
# the traced jaxpr describe the SAME program (plan_check S001/S002).
_RECORDINGS: List[List[Tuple[str, CommSpec]]] = []


@contextlib.contextmanager
def recording() -> Iterator[List[Tuple[str, CommSpec]]]:
    """Collect every (call site, CommSpec) declared while the context is
    active. Nestable: an inner recording does not steal from an outer."""
    rec: List[Tuple[str, CommSpec]] = []
    _RECORDINGS.append(rec)
    try:
        yield rec
    finally:
        _RECORDINGS.remove(rec)


def record(spec: CommSpec, where: str = "") -> None:
    """Append one declared spec to every active recording (no-op when
    none is open)."""
    entry = (where or f"comm:{spec.name}", spec)
    for rec in _RECORDINGS:
        rec.append(entry)


def enforce(spec: CommSpec, where: str = "") -> List[Diagnostic]:
    """Record into the per-trace registry, check, and route through the
    shared diagnostic channel (``FLAGS_static_analysis`` off | warn |
    error)."""
    record(spec, where)
    diags = check_comm_spec(spec)
    if diags:
        emit(diags, where=where or f"comm:{spec.name}")
    return diags
