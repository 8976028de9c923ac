"""Sharded (hybrid-parallel) train-step builder.

This is the TPU-native replacement for the reference's entire hybrid-parallel
execution machinery: ``fleet.distributed_model`` wrapper classes
(``python/paddle/distributed/fleet/meta_parallel/``), the ``EagerReducer``
gradient bucketing (``paddle/fluid/distributed/collective/reducer.h:88``),
GroupSharded stages 1-3 (``fleet/meta_parallel/sharding/``), and the
``HybridParallelOptimizer``. Instead of wrapping the model in per-strategy
classes that hand-issue NCCL calls, we:

1. collect every parameter's ``PartitionSpec`` (tensor-parallel placement from
   the mp layer library, ``paddle_tpu/distributed/fleet/layers/mpu``),
2. extend it with an FSDP ("sharding") axis — ZeRO-3 parameter partitioning is
   just *more sharding* on the same mesh (SURVEY §7: GroupSharded 1/2/3 ⇒
   NamedSharding on params/opt-state),
3. jit ONE pure train step whose inputs/outputs carry those shardings; XLA
   inserts and overlaps every collective (grad allreduce = psum over dp,
   ZeRO gather-on-use = allgather over sharding, TP identity/allreduce over
   mp) on ICI.

Data parallelism is the batch dimension sharded over (dp, sharding): the
"sharding" axis of the reference is a data-parallel axis whose params/opt
state are additionally partitioned.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .functional import functional_call, get_buffers, get_params
from ..nn.layer import Layer

__all__ = ["infer_param_specs", "param_shardings", "shard_params",
           "make_sharded_train_step", "batch_sharding", "TrainStep"]


def _spec_entries(spec, ndim: int):
    entries = list(spec) if spec is not None else []
    entries = entries[:ndim]
    while len(entries) < ndim:
        entries.append(None)
    return entries


def _axes_in(entries):
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update(e if isinstance(e, tuple) else (e,))
    return used


def infer_param_specs(params: Dict[str, jax.Array],
                      user_specs: Dict[str, Optional[P]],
                      mesh: Mesh,
                      fsdp_axis: Optional[str] = "sharding") -> Dict[str, P]:
    """Final PartitionSpec per parameter: the layer-declared TP spec, plus the
    FSDP axis folded onto the largest still-unsharded dim divisible by the
    axis size (ZeRO-3 partitioning; ref group_sharded_stage3.py:59 partitions
    flat param buffers — here partitioning keeps tensor structure so XLA can
    gather-on-use per layer)."""
    out: Dict[str, P] = {}
    fsdp_on = (fsdp_axis is not None and fsdp_axis in mesh.axis_names
               and mesh.shape[fsdp_axis] > 1)
    size = mesh.shape[fsdp_axis] if fsdp_on else 1
    for name, p in params.items():
        entries = _spec_entries(user_specs.get(name), p.ndim)
        # Drop axes the mesh doesn't know about (e.g. 'mp' spec on a dp-only
        # mesh) — the layer library tags specs unconditionally.
        for i, e in enumerate(entries):
            ax = e if isinstance(e, tuple) else (e,) if e is not None else ()
            kept = tuple(a for a in ax if a in mesh.axis_names)
            entries[i] = (kept if len(kept) > 1 else kept[0] if kept else None)
        if fsdp_on and fsdp_axis not in _axes_in(entries):
            best_dim, best_len = -1, 0
            for i, e in enumerate(entries):
                if e is None and p.shape[i] % size == 0 and p.shape[i] > best_len:
                    best_dim, best_len = i, p.shape[i]
            if best_dim >= 0 and best_len >= size:
                entries[best_dim] = fsdp_axis
        out[name] = P(*entries)
    return out


def param_shardings(model: Layer, mesh: Mesh,
                    fsdp_axis: Optional[str] = "sharding"
                    ) -> Dict[str, NamedSharding]:
    params = get_params(model)
    specs = infer_param_specs(params, model.named_param_specs(), mesh,
                              fsdp_axis)
    return {n: NamedSharding(mesh, s) for n, s in specs.items()}


def shard_params(model: Layer, mesh: Mesh,
                 fsdp_axis: Optional[str] = "sharding") -> Dict[str, jax.Array]:
    """Place the model's params on the mesh per their inferred shardings and
    write them back to the Layer tree. Returns the placed param dict."""
    shardings = param_shardings(model, mesh, fsdp_axis)
    params = get_params(model)
    placed = {n: jax.device_put(v, shardings[n]) for n, v in params.items()}
    from .functional import set_params
    set_params(model, placed)
    return placed


def batch_sharding(mesh: Mesh, data_axes: Sequence[str] = ("dp", "sharding"),
                   ndim: int = 2) -> NamedSharding:
    """Batch-dim sharding over the data-parallel axes present in the mesh."""
    axes = tuple(a for a in data_axes if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    first = axes if len(axes) > 1 else (axes[0] if axes else None)
    return NamedSharding(mesh, P(first, *([None] * (ndim - 1))))


def _state_sharding_like(opt_state, pshardings: Dict[str, NamedSharding],
                         mesh: Mesh):
    """Optimizer state sharded like its parameter (ZeRO: opt state partitioned
    identically); scalars replicated."""
    repl = NamedSharding(mesh, P())

    def for_param(name, st):
        # Same-shape-as-param leaves (moments, master weights) get the param
        # sharding; scalar accumulators replicated.
        psh = pshardings[name]
        return {k: (psh if getattr(v, "ndim", 0) > 0 else repl)
                for k, v in st.items()}

    return {
        "step": repl,
        "param_states": {n: for_param(n, st)
                         for n, st in opt_state["param_states"].items()},
    }


class TrainStep:
    """A compiled hybrid-parallel train step.

    step(batch) -> loss  (params/opt state live on device, donated through).
    """

    def __init__(self, model: Layer, optimizer, loss_fn: Callable,
                 mesh: Mesh, fsdp_axis: Optional[str] = "sharding",
                 data_axes: Sequence[str] = ("dp", "sharding"),
                 donate: bool = True):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.data_axes = data_axes

        params = get_params(model, trainable_only=True)
        specs = infer_param_specs(params, model.named_param_specs(), mesh,
                                  fsdp_axis)
        self.pshardings = {n: NamedSharding(mesh, specs[n]) for n in params}
        self._fsdp_axis = fsdp_axis if (
            fsdp_axis is not None and fsdp_axis in mesh.axis_names
            and mesh.shape[fsdp_axis] > 1) else None
        # FLAGS_multislice=flat|hierarchical: explicit 2-tier dp gradient
        # reduction over a slice-aware mesh (distributed/multislice) — the
        # grad computation moves into a shard_map over {slice, dp} and the
        # reduction is issued by the declared reducer instead of GSPMD.
        # Inert (byte-identical step) without a >1 'slice' axis.
        self._multislice = self._resolve_multislice(mesh)
        if self._multislice is not None and "slice" not in self.data_axes:
            self.data_axes = ("slice",) + tuple(self.data_axes)
        def _place(v, sh):
            out = jax.device_put(v, sh)
            if out is v:
                # device_put no-op'd (already placed): make a distinct buffer
                # so donation through the step never deletes the Layer
                # tree's own arrays.
                out = jax.device_put(jnp.copy(v), sh)
            return out

        self.params = {n: _place(v, self.pshardings[n])
                       for n, v in params.items()}
        self.buffers = get_buffers(model)
        # On a multi-device mesh the opt state is BORN sharded like its
        # params (ZeRO opt-state partition): init runs under jit with the
        # target shardings as out_shardings. Built eagerly, every moment
        # first lands whole on the default device (jnp.zeros) — at
        # GPT-1.3B that is 10.5 GB of AdamW moments on chip 0 of a mesh
        # whose per-chip share is a quarter of it. On one device there is
        # nothing to shard and init stays eager.
        if mesh.size > 1:
            ssh = _state_sharding_like(
                jax.eval_shape(optimizer.init, self.params),
                self.pshardings, mesh)
            self.opt_state = jax.jit(optimizer.init, out_shardings=ssh)(
                self.params)
        else:
            self.opt_state = optimizer.init(self.params)
            ssh = _state_sharding_like(self.opt_state, self.pshardings,
                                       mesh)
            self.opt_state = jax.tree_util.tree_map(
                lambda v, s: jax.device_put(v, s), self.opt_state, ssh,
                is_leaf=lambda x: isinstance(x, jax.Array))
        self._state_shardings = ssh

        # 4-arg loss_fn = buffer-threading mode: loss_fn(model, params,
        # buffers, batch) -> (loss, new_buffers). BatchNorm-style running
        # stats flow through the compiled step as explicit state.
        import inspect
        n_args = len(inspect.signature(loss_fn).parameters)
        self._threads_buffers = n_args >= 4

        # The step is COMPOSED, not spliced: framework/step_pipeline.py
        # resolves the live tier flags (offload streaming, ZeRO
        # gather-ahead, decomposed SP, multislice reduction, remat, the
        # health sentinel, telemetry) into an ordered list of contract-
        # bearing passes, each emitting its slice of ONE declared StepPlan
        # and its live graph transform; analysis/pass_check.py's G-rules
        # verify the composition before anything traces.
        from . import step_pipeline as _pipeline
        build = _pipeline.build_for_train_step(
            model, optimizer, loss_fn, mesh, self.data_axes, donate,
            self.params, specs, self.pshardings, ssh, self.buffers,
            self.opt_state, self._fsdp_axis, self._multislice,
            self._threads_buffers)
        _pipeline.compose(build)
        self._gather_specs = build.gather_specs
        self._offload = build.offload
        self._sentinel = build.sentinel
        self.last_stats = None
        self.opt_state = build.opt_state
        # the SDC canary re-executes exactly this (nothing donated, no
        # state mutated) — see canary_step()
        self._compute_grads = build.compute_grads
        self._canary_jit = None
        self._compiled = build.compiled
        self._step_fn = build.step_fn
        self._step_kind = build.step_kind
        self._donate = donate
        self._linted = False
        self._step_count = 0
        self._base_key = jax.random.key(0)
        # Declared composition of this step under the live tier flags —
        # the object analysis/plan_check.py verifies (donation lifetimes,
        # gather-ahead barrier chain, declared-vs-traced collectives) —
        # plus the pass contracts and G diagnostics _maybe_lint reports
        # ahead of the S/D/X rules.
        self.plan = build.plan
        self._pass_contracts = build.contracts
        self._pass_diags = build.diagnostics
        from ..analysis import jaxpr_lint as _jl
        if (_jl.analysis_mode() == "error"
                and any(d.severity == _jl.ERROR for d in self._pass_diags)):
            # composition is illegal — fail at construction, before any
            # trace/compile work happens
            _jl.emit(self._pass_diags, where="sharded.TrainStep.passes")

    def _resolve_multislice(self, mesh):
        """Resolve ``FLAGS_multislice`` against this mesh. Returns
        ``(mode, manual_axes, reducer, world)`` when the 2-tier grad path
        is active, else ``None`` (flag off, or no >1 'slice' axis — the
        step stays byte-identical to the single-mesh path)."""
        from ..core.flags import flag
        mode = str(flag("multislice"))
        if mode == "off" or "slice" not in mesh.axis_names \
                or mesh.shape["slice"] <= 1:
            return None
        if self._fsdp_axis is not None:
            raise ValueError(
                "FLAGS_multislice does not compose with fsdp param "
                "sharding yet: params must be replicated over the manual "
                "{slice, dp} axes (pass fsdp_axis=None or a size-1 "
                "sharding degree)")
        if "dp" not in mesh.axis_names:
            raise ValueError(
                "FLAGS_multislice needs a 'dp' axis for the intra-slice "
                f"reduce-scatter; mesh axes: {mesh.axis_names}")
        manual = ("slice", "dp")
        from ..distributed.multislice import HierarchicalGradReducer
        reducer = HierarchicalGradReducer(axis="dp", dcn_axis="slice")
        world = int(mesh.shape["slice"]) * int(mesh.shape["dp"])
        return mode, manual, reducer, world

    def trace_step(self, batch, lr=None, key=None):
        """Trace the composed step once (no compile) with the comm-spec
        registry recording, completing ``self.plan`` with the hop plans
        declared during the trace. Returns ``(closed_jaxpr,
        donate_argnums)`` — the inputs of ``plan_check.check_plan``."""
        from ..analysis import comm_check
        if lr is None:
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        if key is None:
            key = self._base_key
        with comm_check.recording() as rec:
            if self._step_kind == "offload":
                closed = jax.make_jaxpr(self._step_fn)(
                    self.params, self.buffers, batch, key)
                donate = ()
            elif self._step_kind == "offload_sentinel":
                closed = jax.make_jaxpr(self._step_fn)(
                    self.params, self.buffers, batch, key,
                    jnp.asarray(self._sentinel.guard_vector()))
                donate = ()
            elif self._step_kind == "sentinel":
                closed = jax.make_jaxpr(self._step_fn)(
                    self.params, self.opt_state, self.buffers, batch, lr,
                    key, jnp.asarray(self._sentinel.guard_vector()))
                donate = (0, 1) if self._donate else ()
            else:
                closed = jax.make_jaxpr(self._step_fn)(
                    self.params, self.opt_state, self.buffers, batch, lr,
                    key)
                donate = (0, 1) if self._donate else ()
        self.plan.comm_specs = list(rec)
        return closed, donate

    def compile_step(self, batch, lr=None, key=None):
        """AOT lower+compile the composed step at this batch signature —
        the compiled-HLO verifier's input (``analysis/hlo_check``).
        Returns ``(compiled, donated_leaves)``: the executable whose
        optimized HLO / ``memory_analysis()`` / alias table the X-rules
        read, and the number of flat buffers the dispatch donates into
        it (0 on the offload path — the streaming update owns those
        lifetimes at dispatch level)."""
        if lr is None:
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        if key is None:
            key = self._base_key
        from ..distributed.topology import get_hybrid_mesh, set_hybrid_mesh
        prev_mesh = get_hybrid_mesh()
        set_hybrid_mesh(self.mesh)
        try:
            if self._step_kind == "offload":
                compiled = self._compiled.lower(
                    self.params, self.buffers, batch, key).compile()
                return compiled, 0
            if self._step_kind == "offload_sentinel":
                compiled = self._compiled.lower(
                    self.params, self.buffers, batch, key,
                    jnp.asarray(self._sentinel.guard_vector())).compile()
                return compiled, 0
            if self._step_kind == "sentinel":
                compiled = self._compiled.lower(
                    self.params, self.opt_state, self.buffers, batch, lr,
                    key, jnp.asarray(self._sentinel.guard_vector())
                ).compile()
            else:
                compiled = self._compiled.lower(
                    self.params, self.opt_state, self.buffers, batch, lr,
                    key).compile()
        finally:
            set_hybrid_mesh(prev_mesh)
        donated = 0
        if self._donate:
            donated = (len(jax.tree_util.tree_leaves(self.params))
                       + len(jax.tree_util.tree_leaves(self.opt_state)))
        return compiled, donated

    def _maybe_lint(self, batch, lr, key) -> None:
        """FLAGS_static_analysis: lint the whole train step (fwd + grads +
        update) once at the first batch shape, donation-aware, verify the
        declared StepPlan against the same trace (sharding-flow +
        donation-lifetime rules, analysis/plan_check.py), and — final
        stage — verify what XLA actually built: the step is AOT-compiled
        and its optimized HLO checked against the same plan (X-rules,
        analysis/hlo_check.py — GSPMD-inserted collectives, unrealized
        donations, dtype churn)."""
        from ..analysis import hlo_check, jaxpr_lint, pass_check, plan_check
        from .step_pipeline import AMBIENT_COMM_SPECS
        if self._linted or jaxpr_lint.analysis_mode() == "off":
            return
        self._linted = True
        try:
            closed, donate = self.trace_step(batch, lr, key)
        except Exception:
            return
        # G rules first: the composition's own diagnostics (computed at
        # construction, before tracing) plus the trace-level ownership
        # check — every CommSpec the composed step recorded must be
        # declared by some active pass contract.
        diags = list(self._pass_diags)
        diags += pass_check.check_traced_comm(
            self._pass_contracts, self.plan.comm_specs,
            ambient=AMBIENT_COMM_SPECS, where="sharded.TrainStep.passes")
        diags += jaxpr_lint.lint_jaxpr(closed, donate_argnums=donate,
                                       where="sharded.TrainStep")
        diags += plan_check.check_plan(self.plan, closed,
                                       donate_argnums=donate,
                                       where="sharded.TrainStep")
        try:
            compiled, donated = self.compile_step(batch, lr, key)
        except Exception:
            compiled = None  # the dispatch will surface the compile error
        if compiled is not None:
            diags += hlo_check.check_hlo(self.plan, compiled,
                                         donated_leaves=donated,
                                         where="sharded.TrainStep.hlo")
        jaxpr_lint.emit(diags, where="sharded.TrainStep")

    def step(self, batch, index: Optional[int] = None) -> jax.Array:
        """Run one train step. ``index`` (guarded trainers) pins this
        dispatch's step index — the PRNG stream is
        ``fold_in(base_key, index)`` and ``_step_count`` is set to it —
        so a run that skips poisoned batches keys each *applied* step
        identically to a clean run that never saw them. Default (None)
        keeps the auto-incrementing counter."""
        from ..observability import step_monitor
        tm = step_monitor.current()
        with tm.step():
            return self._step_inner(batch, tm, index=index)

    def _step_inner(self, batch, tm, index: Optional[int] = None
                    ) -> jax.Array:
        ndim_cache: Dict[int, NamedSharding] = {}

        def place(x):
            x = jnp.asarray(x)
            sh = ndim_cache.get(x.ndim)
            if sh is None:
                sh = batch_sharding(self.mesh, self.data_axes, max(x.ndim, 1))
                ndim_cache[x.ndim] = sh
            return jax.device_put(x, sh)

        with tm.phase("h2d"):
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            batch = jax.tree_util.tree_map(place, batch)
        # Trace-time consumers (sharding constraints, CP attention) resolve
        # the mesh via get_hybrid_mesh(); install THIS step's mesh for the
        # call only, so concurrent TrainSteps on different meshes don't
        # corrupt each other.
        from ..distributed.topology import get_hybrid_mesh, set_hybrid_mesh
        prev_mesh = get_hybrid_mesh()
        set_hybrid_mesh(self.mesh)
        try:
            # everything between placement and dispatch that is not the
            # dispatch: step index, key fold, lint, recompile sentinel
            with tm.phase("checks"):
                if index is None:
                    self._step_count += 1
                else:
                    self._step_count = int(index)
                # the flight recorder's step commits carry this global
                # applied index (checkpointed, so it spans incarnations),
                # not just the timeline's process-local step counter
                tm.note("index", self._step_count)
                key = jax.random.fold_in(self._base_key, self._step_count)
                self._maybe_lint(batch, lr, key)
                # Recompile sentinel: params/opt-state signatures are fixed
                # at construction — churn can only come from the batch (and
                # lr dtype), so only those are fingerprinted. The dispatch
                # that first sees a signature is timed as "compile", later
                # ones as "device".
                dispatch_phase = "device"
                if tm.enabled:
                    dispatch_phase = tm.observe_dispatch(
                        ("sharded.TrainStep", id(self)), (batch, lr),
                        where="sharded.TrainStep")
            if self._step_kind == "offload":
                with tm.phase(dispatch_phase):
                    loss, grads, self.buffers = self._compiled(
                        self.params, self.buffers, batch, key)
                self.params, self.opt_state = self._offload.update(
                    self.params, grads, self.opt_state, lr)
            elif self._step_kind == "offload_sentinel":
                # sentinel x offload: the grad-only compiled step computes
                # the fused stats + in-graph verdict; the streamed update
                # is gated ON that verdict at dispatch — an anomalous
                # step's grads are dropped before they ever touch the
                # host-resident moments, so params/opt-state/buffers stay
                # exactly as the fused sentinel path would leave them.
                guard = jnp.asarray(self._sentinel.guard_vector())
                with tm.phase(dispatch_phase):
                    loss, self.last_stats, grads, self.buffers = \
                        self._compiled(self.params, self.buffers, batch,
                                       key, guard)
                applied = bool(np.asarray(self.last_stats)[-1] >= 0.5)
                if applied:
                    self.params, self.opt_state = self._offload.update(
                        self.params, grads, self.opt_state, lr)
            elif self._step_kind == "sentinel":
                guard = jnp.asarray(self._sentinel.guard_vector())
                with tm.phase(dispatch_phase):
                    (loss, self.last_stats, self.params, self.opt_state,
                     self.buffers) = self._compiled(
                        self.params, self.opt_state, self.buffers, batch,
                        lr, key, guard)
            else:
                with tm.phase(dispatch_phase):
                    loss, self.params, self.opt_state, self.buffers = \
                        self._compiled(self.params, self.opt_state,
                                       self.buffers, batch, lr, key)
        finally:
            set_hybrid_mesh(prev_mesh)
        sched = self.optimizer.lr_scheduler
        if sched is not None:
            sched.step()
        return loss

    def sentinel_verdict(self):
        """Classify the last dispatched step's fused stats
        (``fault.health.Verdict``; syncs the stats vector — the read the
        guarded trainer performs in place of/with its loss fetch).
        None when FLAGS_health_sentinel is off or nothing dispatched."""
        if self._sentinel is None or self.last_stats is None:
            return None
        return self._sentinel.verdict(self.last_stats)

    def canary_step(self, batch, index: int):
        """Re-executable grad computation — ``(loss, grads, buffers)``
        with NOTHING donated and no state mutated. Same inputs -> same
        compiled program -> bitwise-equal outputs on a deterministic
        backend; the SDC canary (``fault.health.SdcCanary``) runs this
        twice and a mismatch is silent data corruption."""
        if self._canary_jit is None:
            self._canary_jit = jax.jit(self._compute_grads)
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        key = jax.random.fold_in(self._base_key, int(index))
        from ..distributed.topology import get_hybrid_mesh, set_hybrid_mesh
        prev_mesh = get_hybrid_mesh()
        set_hybrid_mesh(self.mesh)
        try:
            return self._canary_jit(self.params, self.buffers, batch, key)
        finally:
            set_hybrid_mesh(prev_mesh)

    def state_dict(self) -> Dict[str, Any]:
        """Everything needed to resume this step bitwise: params, optimizer
        state (host-resident moments included — arrays are returned as-is,
        the checkpoint capture reads host-committed leaves from host
        memory), buffers, the step counter (the PRNG stream is
        ``fold_in(base_key, step_count)``, so the counter IS the RNG
        state), and the LR-scheduler position."""
        sched = self.optimizer.lr_scheduler
        return {
            "params": dict(self.params),
            "opt_state": self.opt_state,
            "buffers": dict(self.buffers),
            "step_count": int(self._step_count),
            "lr_sched": sched.state_dict() if sched is not None else None,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Restore a :meth:`state_dict` (possibly with numpy leaves from a
        checkpoint). Params/opt state are placed back onto this step's
        shardings; when the offload tier is active, moment leaves are
        placed DIRECTLY into the host memory tier (one H2host transfer,
        never materializing the full moment set in HBM)."""
        self.params = {n: jax.device_put(jnp.asarray(v), self.pshardings[n])
                       for n, v in state["params"].items()}
        ssh = self._state_shardings
        if self._offload is not None:
            kind = self._offload.host_kind
            keys = self._offload._moment_keys
            ssh = {"step": ssh["step"],
                   "param_states": {
                       n: {k: (s.with_memory_kind(kind) if k in keys
                               and getattr(
                                   state["opt_state"]["param_states"]
                                   [n][k], "ndim", 0) > 0 else s)
                           for k, s in st.items()}
                       for n, st in ssh["param_states"].items()}}
        self.opt_state = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(jnp.asarray(v), s),
            state["opt_state"], ssh,
            is_leaf=lambda x: not isinstance(x, dict))
        self.buffers = {n: jnp.asarray(v)
                        for n, v in state.get("buffers", {}).items()}
        self._step_count = int(state["step_count"])
        sched = self.optimizer.lr_scheduler
        if sched is not None and state.get("lr_sched") is not None:
            sched.set_state_dict(state["lr_sched"])

    def sync_to_model(self) -> None:
        """Write the current params/buffers back to the Layer tree (for
        state_dict/save; the reference's sharding stage-3 gathers before save
        — here the arrays stay sharded, jax gathers lazily on host reads)."""
        from .functional import set_buffers, set_params
        set_params(self.model, self.params)
        if self.buffers:
            set_buffers(self.model, self.buffers)


def make_sharded_train_step(model: Layer, optimizer, loss_fn: Callable,
                            mesh: Optional[Mesh] = None,
                            fsdp_axis: Optional[str] = "sharding",
                            data_axes: Sequence[str] = ("dp", "sharding"),
                            donate: bool = True) -> TrainStep:
    """Build a TrainStep. `loss_fn(model, params, batch) -> scalar loss` must
    run the model functionally, e.g.::

        def loss_fn(model, params, batch):
            x, y = batch
            logits = functional_call(model, params, x)
            return F.cross_entropy(logits, y).mean()

    Models with mutable buffers (BatchNorm) use the 4-arg form
    ``loss_fn(model, params, buffers, batch) -> (loss, new_buffers)``::

        def loss_fn(model, params, buffers, batch):
            x, y = batch
            logits, new_buffers = functional_call(
                model, params, x, buffers=buffers, mutable=True)
            return F.cross_entropy(logits, y).mean(), new_buffers
    """
    if mesh is None:
        from ..distributed.topology import get_hybrid_mesh
        mesh = get_hybrid_mesh()
    if mesh is None:
        devs = np.asarray(jax.devices())
        mesh = Mesh(devs.reshape(-1), ("dp",))
    return TrainStep(model, optimizer, loss_fn, mesh, fsdp_axis, data_axes,
                     donate)
