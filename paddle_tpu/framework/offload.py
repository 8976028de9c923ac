"""Host-memory offload tier for optimizer state (ZeRO-Offload on TPU).

The capacity wall this removes: AMP-O2 Adam needs 14 B/param on-chip
(bf16 param 2 + f32 master 4 + f32 moment1 4 + f32 moment2 4) — 18.4 GB
for GPT-1.3B against 15.75 GB of v5e HBM, so the full-depth model cannot
even *initialize* single-chip. Ren et al. (ZeRO-Offload) showed the
moments are the cold half of that state: they are touched exactly once
per step, in a perfectly sequential order, by an elementwise update —
ideal streaming traffic. This module parks them in host memory via JAX
``memory_kind="pinned_host"`` shardings and streams them through HBM one
transformer block at a time, overlapped with the neighbouring blocks'
update compute, turning HBM *capacity* into host-link *bandwidth*:

- placement: moment pytree leaves live host-side
  (``pinned_host`` on TPU; on CPU the default memory IS ``unpinned_host``
  so the machinery degrades to plain buffer plumbing — which is what the
  CPU-mesh parity tests exercise);
- streaming: the per-block update loop prefetches block *i+1*'s moments
  to device while block *i*'s Adam update runs (JAX dispatch is async:
  the H2D DMA and the update executable overlap without any explicit
  stream management), writes block *i*'s new moments back to host, and
  donates every in-flight HBM buffer — peak HBM for optimizer moments is
  ~2 blocks instead of the whole model;
- capacity plan: params, f32 masters, and grads stay resident (they are
  all touched by fwd/bwd, not just the update); see
  :class:`CapacityPlan` and ``tools/hbm_budget.py`` for the static
  accounting.

Wiring: ``FLAGS_offload_optimizer=off|moments`` (registry below) is read
by ``framework.sharded.TrainStep`` (splits its compiled step into a
grad-only jit plus a :class:`StreamingUpdate`) and usable directly
(``tests/test_offload.py``). Any optimizer
that classifies its state via ``Optimizer.offloadable_state_keys()``
participates; ``SGD(multi_precision=True)`` has no moments and is the
zero-transfer resident baseline (≈6 B/param).

Graph hygiene: transfers happen at dispatch level, *between* compiled
programs — never ``device_put`` inside a scan body (analysis rule J012
lints exactly that accident).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.flags import flag

__all__ = ["offload_mode", "host_memory_kind", "StreamingUpdate",
           "group_by_block", "block_key_of", "CapacityPlan",
           "capacity_plan"]


def offload_mode() -> str:
    """Current ``FLAGS_offload_optimizer`` value."""
    return str(flag("offload_optimizer"))


# ---------------------------------------------------------------------------
# Memory-kind plumbing
# ---------------------------------------------------------------------------

_HOST_KINDS = ("pinned_host", "unpinned_host")


def host_memory_kind(device=None) -> Optional[str]:
    """The host memory kind addressable by ``device`` (``pinned_host`` on
    TPU, ``unpinned_host`` on CPU), or None when the runtime exposes no
    host tier (offload then falls back to resident state)."""
    dev = device if device is not None else jax.devices()[0]
    try:
        kinds = [m.kind for m in dev.addressable_memories()]
    except Exception:
        return None
    for k in _HOST_KINDS:
        if k in kinds:
            return k
    return None


def _host_sharding(sh, kind: str):
    return sh.with_memory_kind(kind)


def _is_host_committed(x, kind: str) -> bool:
    return getattr(getattr(x, "sharding", None), "memory_kind", None) == kind


# ---------------------------------------------------------------------------
# Block grouping: the streaming unit is one transformer block
# ---------------------------------------------------------------------------

_INT_SEG = re.compile(r"^\d+$")


def block_key_of(name: str) -> Tuple[str, int]:
    """Grouping key for a parameter name: the path up to and including its
    first integer segment — ``gpt.h.7.attn.qkv_proj.weight`` -> ``("gpt.h",
    7)``, so each transformer block streams as one unit. Names with no
    integer segment (embeddings, final norm, head) share one ``("", -1)``
    group."""
    parts = name.split(".")
    for i, seg in enumerate(parts):
        if _INT_SEG.match(seg):
            return (".".join(parts[:i]), int(seg))
    return ("", -1)


def group_by_block(names: Sequence[str]) -> List[Tuple[Tuple[str, int],
                                                       List[str]]]:
    """Ordered (block_key, [param names]) groups. Blocks are ordered by
    (prefix, index) so the stream walks the model front to back — the same
    order the backward pass finished producing grads, keeping the prefetch
    distance short."""
    groups: Dict[Tuple[str, int], List[str]] = {}
    for n in names:
        groups.setdefault(block_key_of(n), []).append(n)
    return [(k, groups[k]) for k in sorted(groups)]


def plan_nodes_for(param_names: Sequence[str], clip: bool = False):
    """The streaming update's dispatch sequence as declared
    :class:`~paddle_tpu.analysis.plan_check.PlanNode`\\ s, from the
    parameter name set alone — the step-pipeline's offload pass emits
    these in plan-only composition, and the live
    :meth:`StreamingUpdate.plan_nodes` delegates here. Per block: H2D
    moment prefetch, the donating block update (params/grads/in-flight
    moments), D2H write-back donating the fresh device moments — the
    shape the step-plan verifier's donation-lifetime walk (D001/D002)
    checks."""
    from ..analysis.plan_check import PlanNode
    nodes = []
    if clip:
        nodes.append(PlanNode("offload.clip", reads=("grads",),
                              writes=("grads",)))
    groups = group_by_block(list(param_names))
    for i in range(len(groups)):
        nodes.append(PlanNode(
            f"offload.prefetch[{i}]",
            reads=(f"host_moments[{i}]",),
            writes=(f"moments[{i}]",)))
        nodes.append(PlanNode(
            f"offload.update[{i}]",
            reads=("opt_scalars",),
            donates=(f"params[{i}]", f"grads[{i}]", f"moments[{i}]"),
            writes=(f"params[{i}]", f"moments[{i}]")))
        nodes.append(PlanNode(
            f"offload.writeback[{i}]",
            donates=(f"moments[{i}]",),
            writes=(f"host_moments[{i}]",)))
    return nodes


# ---------------------------------------------------------------------------
# Capacity plan
# ---------------------------------------------------------------------------

class CapacityPlan:
    """Byte accounting of one (params, opt_state) placement decision."""

    def __init__(self, rows: Dict[str, int], mode: str, n_blocks: int):
        self.rows = dict(rows)
        self.mode = mode
        self.n_blocks = n_blocks

    @property
    def device_bytes(self) -> int:
        return sum(v for k, v in self.rows.items()
                   if not k.startswith("host_"))

    @property
    def host_bytes(self) -> int:
        return sum(v for k, v in self.rows.items() if k.startswith("host_"))

    def to_json(self) -> Dict[str, Any]:
        return {"mode": self.mode, "n_blocks": self.n_blocks,
                "device_gb": round(self.device_bytes / 2**30, 3),
                "host_gb": round(self.host_bytes / 2**30, 3),
                "rows_gb": {k: round(v / 2**30, 3)
                            for k, v in self.rows.items()}}


def capacity_plan(params: Dict[str, jax.Array], opt,
                  mode: Optional[str] = None) -> CapacityPlan:
    """Static plan from live param arrays + an optimizer instance: which
    state bytes sit in HBM vs host under ``mode``. Moments in flight are
    counted as the two largest blocks (current + prefetched)."""
    mode = offload_mode() if mode is None else mode
    mkeys = set(getattr(opt, "offloadable_state_keys", lambda: ())())
    pbytes = sum(v.size * v.dtype.itemsize for v in params.values())
    master = sum(v.size * 4 for v in params.values()
                 if opt._needs_master(v))
    # per-state-key bytes from the optimizer's own init shapes
    moment = 0
    for v in params.values():
        shapes = jax.eval_shape(opt._init_param_state, v)
        moment += sum(s.size * s.dtype.itemsize
                      for k, s in shapes.items() if k in mkeys)
    groups = group_by_block(list(params))
    rows = {"params": pbytes, "grads": pbytes, "master": master}
    if mode == "moments" and moment:
        per_block = []
        for _, names in groups:
            b = 0
            for n in names:
                shapes = jax.eval_shape(opt._init_param_state, params[n])
                b += sum(s.size * s.dtype.itemsize
                         for k, s in shapes.items() if k in mkeys)
            per_block.append(b)
        rows["host_moments"] = moment
        rows["moments_in_flight"] = sum(sorted(per_block)[-2:])
    else:
        rows["moments"] = moment
    return CapacityPlan(rows, mode, len(groups))


# ---------------------------------------------------------------------------
# Streaming update
# ---------------------------------------------------------------------------

class StreamingUpdate:
    """Per-block optimizer update with host-resident moments.

    ``init_state(params)`` builds optimizer state with moment leaves placed
    host-side as they are created (never materializing the full moment set
    in HBM); ``update(params, grads, state, lr)`` is a drop-in replacement
    for ``opt.apply_gradients`` whose returned state again has host-side
    moments. The state pytree structure is IDENTICAL to the resident
    optimizer's — checkpointing (``np.asarray`` gathers host or device
    arrays alike) and ``set_state_dict`` round-trip unchanged; ``place``
    re-homes a freshly loaded (device-side) state.
    """

    def __init__(self, opt, host_kind: Optional[str] = None):
        self.opt = opt
        self.host_kind = host_kind or host_memory_kind()
        if self.host_kind is None:
            raise RuntimeError(
                "no host memory tier addressable by the default device; "
                "use FLAGS_offload_optimizer=off")
        self._moment_keys = frozenset(opt.offloadable_state_keys())
        self._donate_ok = True
        opt_ref = opt

        def _block(p_blk, g_blk, st_blk, step, lr):
            state = {"step": step, "param_states": st_blk}
            new_p, new_state = opt_ref.apply_gradients(p_blk, g_blk, state,
                                                       lr, clip=False)
            return new_p, new_state["param_states"]

        # One executable per block *structure*: homogeneous trunk blocks
        # share a single compilation. Donation frees the old params, the
        # consumed grads, and the in-flight HBM moment buffers.
        self._block_fn = jax.jit(_block, donate_argnums=(0, 1, 2))
        self._clip_fn = jax.jit(opt.grad_clip) if opt.grad_clip is not None \
            else None

    # -- placement ----------------------------------------------------------

    def _offloadable(self, key: str, v) -> bool:
        return key in self._moment_keys and getattr(v, "ndim", 0) > 0

    def _to_host(self, v: jax.Array, donate: bool) -> jax.Array:
        if _is_host_committed(v, self.host_kind):
            return v
        tgt = _host_sharding(v.sharding, self.host_kind)
        if donate and self._donate_ok:
            try:
                return jax.device_put(v, tgt, donate=True)
            except Exception:
                # donation across memory kinds is best-effort in the
                # runtime; fall back to plain transfers (GC frees the
                # device buffer once the caller drops its reference)
                self._donate_ok = False
        return jax.device_put(v, tgt)

    def _to_device(self, v: jax.Array, like: jax.Array) -> jax.Array:
        """H2D prefetch onto ``like``'s sharding. The result must be a
        buffer the block update can safely donate: when device_put no-ops
        (CPU, where host IS device memory), copy so donation can never
        alias the caller's live host moments."""
        out = jax.device_put(v, like.sharding)
        if out is v:
            out = jnp.copy(v)
        return out

    def place(self, opt_state) -> Any:
        """Move the state's moment leaves host-side (donating the device
        buffers). Idempotent; non-moment leaves untouched."""
        ps = {n: {k: (self._to_host(v, donate=True)
                      if self._offloadable(k, v) else v)
                  for k, v in st.items()}
              for n, st in opt_state["param_states"].items()}
        return {"step": opt_state["step"], "param_states": ps}

    def init_state(self, params: Dict[str, jax.Array]) -> Any:
        """``opt.init`` with moments born host-side, one parameter at a
        time — the transient HBM peak is a single parameter's moments, so
        a model whose FULL moment set exceeds HBM can still initialize."""
        pstates = {}
        for n, p in params.items():
            st = self.opt._init_full_param_state(p)
            pstates[n] = {k: (self._to_host(v, donate=True)
                              if self._offloadable(k, v) else v)
                          for k, v in st.items()}
        return {"step": jnp.zeros((), jnp.int32), "param_states": pstates}

    # -- declared plan ------------------------------------------------------

    def plan_nodes(self, param_names: Sequence[str]):
        """The streaming update's dispatch sequence as declared
        :class:`~paddle_tpu.analysis.plan_check.PlanNode`\\ s, for the
        step-plan verifier's donation-lifetime walk (rules D001/D002).
        Mirrors :meth:`update` exactly."""
        return plan_nodes_for(param_names,
                              clip=self._clip_fn is not None)

    # -- the streaming loop -------------------------------------------------

    def _prefetch(self, names, params, pstates):
        return {n: {k: self._to_device(v, params[n])
                    for k, v in pstates[n].items()
                    if self._offloadable(k, v)}
                for n in names if n in pstates}

    def update(self, params: Dict[str, jax.Array],
               grads: Dict[str, jax.Array], opt_state, lr):
        """apply_gradients, streamed per block.

        Dispatch order per block i: (1) issue block i+1's H2D moment
        prefetch, (2) launch block i's update (compute overlaps the DMA),
        (3) issue block i's D2H moment write-back donating the device
        buffer. Global-norm grad clip runs ONCE over the full grad tree
        before any block update (a per-block clip would change the norm).
        """
        from ..observability import step_monitor
        tm = step_monitor.current()
        if self._clip_fn is not None:
            grads = self._clip_fn(grads)
        lr = jnp.asarray(lr, jnp.float32)
        step = opt_state["step"]
        pstates = opt_state["param_states"]
        groups = [(k, [n for n in names if grads.get(n) is not None])
                  for k, names in group_by_block(list(params))]
        groups = [(k, names) for k, names in groups if names]
        new_params = dict(params)
        new_pstates = dict(pstates)
        with tm.phase("offload_in"):
            inflight = self._prefetch(groups[0][1], params, pstates) \
                if groups else {}
        for i, (_, names) in enumerate(groups):
            dev_moments = inflight
            if i + 1 < len(groups):
                # issue next block's H2D now — it rides the host link
                # while this block's update occupies the core
                with tm.phase("offload_in"):
                    inflight = self._prefetch(groups[i + 1][1], params,
                                              pstates)
            p_blk = {n: params[n] for n in names}
            g_blk = {n: grads[n] for n in names}
            st_blk = {}
            for n in names:
                st = pstates.get(n, {})
                st_blk[n] = {**{k: v for k, v in st.items()
                                if not self._offloadable(k, v)},
                             **dev_moments.get(n, {})}
            with tm.phase("device"):
                new_p_blk, new_st_blk = self._block_fn(p_blk, g_blk, st_blk,
                                                       step, lr)
            with tm.phase("offload_out"):
                for n in names:
                    new_pstates[n] = {
                        k: (self._to_host(v, donate=True)
                            if self._offloadable(k, v) else v)
                        for k, v in new_st_blk[n].items()}
            new_params.update(new_p_blk)
        return new_params, {"step": step + jnp.ones((), jnp.int32),
                            "param_states": new_pstates}
