"""Hierarchical (2-tier) data-parallel gradient reduction.

The single-axis flat path (``overlap.BucketedGradReducer``) issues one
``psum`` per bucket over one data axis. Across pod slices that is the
wrong shape twice over: a flat reduction spanning the ``slice`` axis
moves the **full bucket** over DCN (the slowest link in the system), and
buckets sized for ICI latency are far too small for the cross-slice RTT.

:class:`HierarchicalGradReducer` reduces each bucket in three declared
stages instead::

    intra-slice ICI reduce-scatter   (bucket -> 1/ici_size shard, reduced)
    inter-slice DCN allreduce        (only the shard crosses DCN)
    intra-slice ICI all-gather       (shard -> full reduced bucket)

so per-step DCN traffic is ``bucket_bytes / ici_size`` — the property
``analysis.comm_check`` rule C004 enforces (the naive flat-over-DCN plan
fires it). Buckets are sized per link class: the DCN default
(``FLAGS_multislice_dcn_bucket_mb``) is larger than the ICI default to
amortize the cross-slice latency floor (C005).

Numerics: the hierarchical result is **bitwise order-independent** across
bucket permutations (flattening never changes any element's reduction
order) and **bitwise identical** to the flat per-axis baseline
(``mode="flat"``): both associate each element's sum as
``(sum within slice) + (across slices)`` — the reduce-scatter only
changes *where* each shard's identical rank-order sum is computed, not
its association. The flat baseline still moves the whole bucket over
DCN; the hierarchical plan moves 1/ici_size of it. That pairing is what
the 2-slice dryrun (``tests/test_multislice.py``) asserts bitwise.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...analysis.comm_check import (DCN_ALLREDUCE, FLAT_ICI_ALLREDUCE,
                                    SLICE_ALL_GATHER, SLICE_REDUCE_SCATTER)
from ...core.flags import flag
from ..overlap import BucketedGradReducer
from .topology import SLICE_AXIS

__all__ = ["HierarchicalGradReducer", "MULTISLICE_COMM_SPECS"]

# The CommSpec names one reduction pass of this module may register —
# the three hierarchical stages plus the flat A/B baseline (canonical
# values in ``analysis.comm_check``). The step pipeline's
# ``multislice_reduce`` pass contract consumes this tuple, so the
# trace-level G003 ownership check follows these stages by construction.
MULTISLICE_COMM_SPECS = (SLICE_REDUCE_SCATTER, DCN_ALLREDUCE,
                         SLICE_ALL_GATHER, FLAT_ICI_ALLREDUCE)


class HierarchicalGradReducer(BucketedGradReducer):
    """Bucketed 2-tier reduction inside a shard_map with both the ICI
    data axis and the DCN slice axis bound.

    ``axis`` (inherited) is the intra-slice ICI data axis; ``dcn_axis``
    is the between-slice axis. ``bucket_bytes`` defaults to
    ``FLAGS_multislice_dcn_bucket_mb`` — the DCN link class wants larger
    buckets than ``FLAGS_comm_overlap_bucket_mb`` sizes for ICI.
    """

    def __init__(self, axis: str = "dp", dcn_axis: str = SLICE_AXIS,
                 bucket_bytes: Optional[int] = None):
        if bucket_bytes is None:
            bucket_bytes = int(flag("multislice_dcn_bucket_mb")) << 20
        super().__init__(axis=axis, bucket_bytes=bucket_bytes)
        self.dcn_axis = dcn_axis

    # -- static accounting -------------------------------------------------

    def _bucket_specs(self, nbytes: int, ici_size: int, dcn_size: int,
                      mode: str) -> List[Any]:
        """The declared CommSpec stages of ONE bucket's reduction pass."""
        from ...analysis import comm_check
        if mode == "hierarchical":
            shard = -(-nbytes // max(ici_size, 1))
            return [
                comm_check.spec_for_slice_reduce_scatter(
                    nbytes, ici_size, axis=self.axis),
                comm_check.spec_for_dcn_allreduce(
                    shard, dcn_size, reduced_from_bytes=nbytes,
                    ici_size=ici_size, axis=self.dcn_axis),
                comm_check.spec_for_slice_all_gather(
                    nbytes, ici_size, axis=self.axis),
            ]
        # flat: a per-axis psum of the FULL bucket — the ICI ring
        # allreduce is fine, the DCN stage carries the whole bucket and
        # C004 fires on it
        shard = -(-nbytes // max(ici_size, 1))
        return [
            comm_check.CommSpec(
                name=FLAT_ICI_ALLREDUCE, axis_size=ici_size,
                hops=2 * max(ici_size - 1, 0), bytes_per_hop=shard,
                collective_bytes=2 * max(ici_size - 1, 0) * shard,
                flops_per_hop=0, directions=1, axis=self.axis,
                link=comm_check.link_class(self.axis),
                payload_bytes=nbytes),
            comm_check.spec_for_dcn_allreduce(
                nbytes, dcn_size, reduced_from_bytes=nbytes,
                ici_size=ici_size, axis=self.dcn_axis),
        ]

    def _bucket_bytes_of(self, grads: Dict[str, Any],
                         names: List[str]) -> int:
        return sum(int(grads[n].size) * jnp.dtype(grads[n].dtype).itemsize
                   for n in names)

    def hop_plan(self, grads: Dict[str, Any], ici_size: int, dcn_size: int,
                 mode: str = "hierarchical") -> List[Any]:
        """The declared CommSpec sequence of one reduction pass — pure
        arithmetic over the grad shapes (no tracing), the same specs
        :meth:`reduce_in_axes` enforces at its call site."""
        specs: List[Any] = []
        for names in self.bucketize(grads):
            specs += self._bucket_specs(
                self._bucket_bytes_of(grads, names), ici_size, dcn_size,
                mode)
        return specs

    def dcn_bytes_per_step(self, grads: Dict[str, Any], ici_size: int,
                           dcn_size: int,
                           mode: str = "hierarchical") -> int:
        """Per-rank bytes crossing DCN in one reduction pass: the sum of
        the dcn-class stages' payloads."""
        return sum(s.payload_bytes
                   for s in self.hop_plan(grads, ici_size, dcn_size, mode)
                   if s.link == "dcn")

    # -- the in-axis reduction ---------------------------------------------

    def reduce_in_axes(self, grads: Dict[str, jax.Array],
                       mode: str = "hierarchical"
                       ) -> Dict[str, jax.Array]:
        """Reduce (sum) every grad over BOTH axes inside a shard_map with
        ``self.axis`` (ICI) and ``self.dcn_axis`` (DCN) bound.

        ``mode="hierarchical"``: reduce-scatter over the ICI axis (bucket
        padded to a multiple of the axis size), allreduce the 1/ici shard
        over the DCN axis, all-gather back. ``mode="flat"``: the naive
        per-axis flat psum baseline — same values bitwise, full bucket
        over DCN (the plan C004 flags). Both declare their hop plans
        through ``comm_check.enforce`` at trace time.
        """
        if mode not in ("hierarchical", "flat"):
            raise ValueError(f"mode must be 'hierarchical' or 'flat', "
                             f"got {mode!r}")
        from ...analysis import comm_check
        ici = int(lax.psum(1, self.axis))
        dcn = int(lax.psum(1, self.dcn_axis))
        out = dict(grads)
        for names in self.bucketize(grads):
            gs = [grads[n] for n in names]
            flat = self._flatten(gs)
            nbytes = int(flat.size) * jnp.dtype(flat.dtype).itemsize
            for spec in self._bucket_specs(nbytes, ici, dcn, mode):
                comm_check.enforce(spec, where=f"multislice.{mode}")
            if mode == "hierarchical":
                red = self._rs_ar_ag(flat, ici)
            else:
                red = lax.psum(flat, self.axis)
                red = lax.psum(red, self.dcn_axis)
            for n, g in zip(names, self._unflatten(red, gs)):
                out[n] = g
        return out

    def _rs_ar_ag(self, flat: jax.Array, ici: int) -> jax.Array:
        """RS(ici) -> AR(dcn) -> AG(ici) of one flat bucket, padded to a
        multiple of the ICI axis size (bucketize produces arbitrary
        lengths)."""
        pad = (-int(flat.size)) % max(ici, 1)
        padded = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)]) if pad else flat
        shard = lax.psum_scatter(padded, self.axis, tiled=True)
        shard = lax.psum(shard, self.dcn_axis)
        red = lax.all_gather(shard, self.axis, tiled=True)
        return red[:flat.size] if pad else red
