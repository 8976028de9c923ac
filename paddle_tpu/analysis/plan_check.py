"""Step-plan verifier: static sharding-flow + donation-lifetime analysis.

The flag-gated tiers (offload streaming, comm-overlap decomposition, ZeRO
gather-ahead, ring-CP, remat) each splice into ``framework.sharded.
TrainStep`` independently; the bugs that burn a pod show up only in the
*composition* — a buffer donated by one tier and read by another, a
gather-ahead chain with a missing barrier edge, a decomposed collective
whose declared hop plan drifted from what actually traces. This module
checks the whole composed step statically, on a CPU checkout:

- a declared :class:`StepPlan`, assembled by ``sharded.TrainStep`` /
  ``framework/offload.py`` / ``distributed/overlap.py`` from the live
  flag state: the dispatch-level node sequence (what each compiled
  sub-program reads / writes / donates), the gather-ahead barrier plan,
  every :class:`~.comm_check.CommSpec` recorded while the step traced,
  and optionally a ``tools/hbm_budget.py`` capacity plan;
- **S-rules** (sharding-flow) cross-check the plan against the traced
  step jaxpr: every manual collective in the graph must have a declared
  CommSpec (S001), every declaration must have trace evidence (S002),
  and no fsdp-sharded parameter may be gathered on the step path outside
  the declared gather-ahead plan (S003 — the accidental all-gather);
- **D-rules** (donation / buffer lifetime) walk the node sequence:
  reads-after-donation across sub-programs (D001), double-donation when
  two tiers claim the same buffer (D002), a gather-ahead
  ``optimization_barrier`` chain that is not total or not acyclic
  (D003), and a composed capacity plan that does not fit the chip
  (D004).

``tools/lint_graph.py --matrix`` enumerates every supported combination
of the six tier flags, builds each StepPlan on the 8-device virtual
mesh, and runs these checks plus ``comm_check`` and ``hbm_budget``
against the composition. Rule catalog: ``analysis/RULES.md``.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

from ._jaxpr_utils import inner_jaxprs
from .jaxpr_lint import Diagnostic, ERROR, _SEV_ORDER, emit

__all__ = [
    "ParamInfo", "PlanNode", "GatherPlan", "StepPlan", "JaxprFacts",
    "collect_jaxpr_facts", "check_plan", "check_capacity", "enforce",
    "register_plan_rule", "all_plan_rules", "TIER_FLAGS",
    "iter_tier_combos", "normalize_combo",
]


# ---------------------------------------------------------------------------
# The declared plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamInfo:
    """Shape + declared PartitionSpec of one step parameter."""

    shape: Tuple[int, ...]
    spec: Any  # jax PartitionSpec (or None for replicated)


@dataclass(frozen=True)
class PlanNode:
    """One dispatch-level sub-program of the composed step.

    Buffer names are logical ("params", "grads", "moments[3]"); an
    indexed name overlaps its unindexed base — donating "params" poisons
    every "params[i]" and vice versa.
    """

    name: str
    reads: Tuple[str, ...] = ()
    writes: Tuple[str, ...] = ()
    donates: Tuple[str, ...] = ()


@dataclass
class GatherPlan:
    """Declared ZeRO-3 gather-ahead ordering (overlap.zero_gather_ahead):
    which blocks carry gathered params (``anchored``) and the
    optimization_barrier edges tying block *i*'s gather into block
    *i - depth*'s."""

    depth: int
    anchored: Tuple[bool, ...]            # per block, in stream order
    edges: Tuple[Tuple[int, int], ...]    # (earlier block, later block)
    params: Dict[str, Any]                # name -> gathered PartitionSpec


@dataclass
class StepPlan:
    """The declared composition of one TrainStep under the live flags."""

    flags: Dict[str, Any] = field(default_factory=dict)
    mesh_axes: Dict[str, int] = field(default_factory=dict)
    fsdp_axis: Optional[str] = None
    params: Dict[str, ParamInfo] = field(default_factory=dict)
    nodes: List[PlanNode] = field(default_factory=list)
    gather: Optional[GatherPlan] = None
    # (call-site, CommSpec) pairs recorded by comm_check during the trace
    comm_specs: List[Tuple[str, Any]] = field(default_factory=list)
    # tools/hbm_budget.py plan dict ("fits", "device_gb", "budget_gb", ...)
    capacity: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        return {
            "flags": {k: (v if isinstance(v, (int, float, str, bool))
                          else str(v)) for k, v in self.flags.items()},
            "mesh_axes": dict(self.mesh_axes),
            "fsdp_axis": self.fsdp_axis,
            "n_params": len(self.params),
            "nodes": [n.name for n in self.nodes],
            "gather": None if self.gather is None else {
                "depth": self.gather.depth,
                "blocks": len(self.gather.anchored),
                "edges": [list(e) for e in self.gather.edges],
                "params": sorted(self.gather.params),
            },
            "comm_specs": [{"where": w, "name": s.name, "axis": s.axis,
                            "hops": s.hops}
                           for w, s in self.comm_specs],
            "capacity": self.capacity,
        }


# ---------------------------------------------------------------------------
# Jaxpr fact extraction (the "actual" side of declared-vs-actual)
# ---------------------------------------------------------------------------

_MANUAL_COLLECTIVES = frozenset({
    "ppermute", "psum", "psum_scatter", "all_gather", "all_to_all",
    "reduce_scatter", "all_reduce", "pmax", "pmin",
})


def _eqn_axes(eqn) -> Tuple[str, ...]:
    """Named mesh axes a collective equation operates over."""
    axes: List[str] = []
    for key in ("axis_name", "axes"):
        val = eqn.params.get(key)
        if val is None:
            continue
        for a in (val if isinstance(val, (tuple, list)) else (val,)):
            if isinstance(a, str):
                axes.append(a)
    return tuple(axes)


@dataclass
class JaxprFacts:
    """What the traced step graph actually contains."""

    # mesh axis -> collective primitive names seen on it
    collectives: Dict[str, List[str]] = field(default_factory=dict)
    # (operand shape, PartitionSpec) per sharding_constraint eqn
    constraints: List[Tuple[Tuple[int, ...], Any]] = field(
        default_factory=list)
    barriers: int = 0
    eqn_count: int = 0


def collect_jaxpr_facts(closed_jaxpr) -> JaxprFacts:
    """Recursive walk of one ClosedJaxpr collecting the S/D-relevant
    equations. Inner jaxprs are memoized — jax caches them, and a shared
    pjit body walked twice would double every count."""
    facts = JaxprFacts()
    seen = set()

    def walk(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            facts.eqn_count += 1
            name = eqn.primitive.name
            if name in _MANUAL_COLLECTIVES:
                for ax in _eqn_axes(eqn):
                    facts.collectives.setdefault(ax, []).append(name)
            elif name == "sharding_constraint":
                sh = eqn.params.get("sharding")
                spec = getattr(sh, "spec", None)
                aval = getattr(eqn.invars[0], "aval", None)
                if spec is not None and hasattr(aval, "shape"):
                    facts.constraints.append(
                        (tuple(int(d) for d in aval.shape), spec))
            elif name == "optimization_barrier":
                facts.barriers += 1
            for _, inner in inner_jaxprs(eqn):
                walk(inner.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return facts


# ---------------------------------------------------------------------------
# Rule registry (S/D families)
# ---------------------------------------------------------------------------

@dataclass
class PlanContext:
    plan: StepPlan
    facts: Optional[JaxprFacts]  # None when the step was not traced
    donate_argnums: Tuple[int, ...] = ()


@dataclass
class _PlanRule:
    rule_id: str
    name: str
    severity: str
    doc: str
    fn: Callable[[PlanContext], Iterable[Diagnostic]]


_PLAN_RULES: Dict[str, _PlanRule] = {}


def register_plan_rule(rule_id: str, name: str, severity: str, doc: str):
    def wrap(fn):
        _PLAN_RULES[rule_id] = _PlanRule(rule_id, name, severity, doc, fn)
        return fn

    return wrap


def all_plan_rules() -> List[_PlanRule]:
    return [_PLAN_RULES[k] for k in sorted(_PLAN_RULES)]


def _diag(rule: _PlanRule, message: str, hint: str = "") -> Diagnostic:
    return Diagnostic(rule=rule.rule_id, name=rule.name,
                      severity=rule.severity, message=message, hint=hint)


# ---------------------------------------------------------------------------
# Spec helpers
# ---------------------------------------------------------------------------

def _norm_spec(spec) -> Tuple:
    """PartitionSpec -> comparable tuple with trailing Nones stripped
    (P('x', None) and P('x') describe the same placement)."""
    entries = []
    for e in (tuple(spec) if spec is not None else ()):
        if isinstance(e, tuple):
            entries.append(tuple(e) if len(e) > 1
                           else (e[0] if e else None))
        else:
            entries.append(e)
    while entries and entries[-1] is None:
        entries.pop()
    return tuple(entries)


def _spec_axes(spec) -> set:
    used = set()
    for e in (tuple(spec) if spec is not None else ()):
        if e is None:
            continue
        used.update(e if isinstance(e, tuple) else (e,))
    return used


def _gathered_spec(spec, axis: str):
    from ..distributed.overlap import spec_without_axis
    return spec_without_axis(spec, axis)


# ---------------------------------------------------------------------------
# S-rules: sharding flow
# ---------------------------------------------------------------------------

@register_plan_rule(
    "S001", "undeclared-collective", ERROR,
    "a manual collective traced on the step path over a mesh axis with "
    "no declared CommSpec — an implicit reshard/overlap loop the static "
    "ICI accounting never saw")
def _rule_undeclared_collective(ctx: PlanContext):
    rule = _PLAN_RULES["S001"]
    if ctx.facts is None:
        return
    declared_axes = {s.axis for _, s in ctx.plan.comm_specs}
    for ax, prims in sorted(ctx.facts.collectives.items()):
        if ax in declared_axes:
            continue
        if ctx.plan.mesh_axes.get(ax, 2) <= 1:
            continue  # degenerate axis: the collective is a no-op
        counts = {p: prims.count(p) for p in sorted(set(prims))}
        yield _diag(
            rule,
            f"{len(prims)} collective equation(s) over mesh axis {ax!r} "
            f"({', '.join(f'{k} x{v}' for k, v in counts.items())}) with "
            "no declared CommSpec on that axis — the hop plan was never "
            "accounted against the ICI budget",
            hint="declare the hop plan (analysis.comm_check.CommSpec) at "
                 "the call site via comm_check.enforce, or route the "
                 "collective through distributed/overlap.py")


@register_plan_rule(
    "S002", "phantom-declaration", ERROR,
    "a declared CommSpec or gather-ahead entry with no trace evidence — "
    "the plan promises communication the step graph does not contain")
def _rule_phantom_declaration(ctx: PlanContext):
    rule = _PLAN_RULES["S002"]
    if ctx.facts is None:
        return
    plan = ctx.plan
    for where, spec in plan.comm_specs:
        if spec.hops == 0 or spec.axis_size <= 1:
            continue
        if not ctx.facts.collectives.get(spec.axis):
            yield _diag(
                rule,
                f"CommSpec {spec.name!r} declared at {where} promises "
                f"{spec.hops} hop(s) over axis {spec.axis!r}, but the "
                "traced step contains no collective on that axis — stale "
                "or phantom declaration",
                hint="drop the declaration or fix the call site so the "
                     "decomposed loop actually traces")
    if plan.gather is not None and plan.fsdp_axis is not None:
        matched = _match_gather_constraints(plan, ctx.facts)
        for name in sorted(plan.gather.params):
            if name not in matched:
                info = plan.params.get(name)
                yield _diag(
                    rule,
                    f"gather-ahead declares param {name!r} "
                    f"(shape {getattr(info, 'shape', '?')}) but no "
                    "matching gathered sharding constraint was traced — "
                    "the prefetch the plan promises does not exist",
                    hint="the gather plan must be assembled from the same "
                         "_gather_specs the step closure consumes "
                         "(overlap.gather_ahead_plan)")


def _match_gather_constraints(plan: StepPlan, facts: JaxprFacts):
    """Greedy match of declared gather-ahead params onto traced
    sharding-constraint eqns by (shape, gathered spec). Returns the set
    of matched param names; each traced constraint satisfies at most one
    declaration, so surplus constraints stay visible to S003."""
    budget: Dict[Tuple, int] = {}
    for shape, spec in facts.constraints:
        key = (shape, _norm_spec(spec))
        budget[key] = budget.get(key, 0) + 1
    matched = set()
    if plan.gather is None or plan.fsdp_axis is None:
        return matched
    for name, gspec in plan.gather.params.items():
        info = plan.params.get(name)
        if info is None:
            continue
        key = (info.shape, _norm_spec(gspec))
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            matched.add(name)
    return matched


@register_plan_rule(
    "S003", "undeclared-param-gather", ERROR,
    "an fsdp-sharded parameter is all-gathered (its sharding constraint "
    "drops the fsdp axis) on the step path outside the declared "
    "gather-ahead plan — an accidental full materialization")
def _rule_undeclared_param_gather(ctx: PlanContext):
    rule = _PLAN_RULES["S003"]
    plan = ctx.plan
    if ctx.facts is None or plan.fsdp_axis is None:
        return
    axis = plan.fsdp_axis
    # (shape, gathered spec) classes of the fsdp-sharded params
    classes: Dict[Tuple, List[str]] = {}
    for name, info in plan.params.items():
        if axis not in _spec_axes(info.spec):
            continue
        key = (info.shape, _norm_spec(_gathered_spec(info.spec, axis)))
        classes.setdefault(key, []).append(name)
    declared: Dict[Tuple, int] = {}
    if plan.gather is not None:
        for name in plan.gather.params:
            info = plan.params.get(name)
            if info is None or axis not in _spec_axes(info.spec):
                continue
            key = (info.shape, _norm_spec(_gathered_spec(info.spec, axis)))
            declared[key] = declared.get(key, 0) + 1
    traced: Dict[Tuple, int] = {}
    for shape, spec in ctx.facts.constraints:
        key = (shape, _norm_spec(spec))
        if key in classes:
            traced[key] = traced.get(key, 0) + 1
    for key, names in sorted(classes.items()):
        # Each declared gather legitimately traces up to twice: the
        # forward with_sharding_constraint plus its AD transpose, which
        # re-constrains the grad cotangent to the same (gathered) spec
        # before the reduce-scatter.
        extra = traced.get(key, 0) - 2 * declared.get(key, 0)
        if extra > 0:
            shape, _ = key
            yield _diag(
                rule,
                f"{extra} traced sharding constraint(s) gather an "
                f"fsdp-sharded param of shape {shape} (candidates: "
                f"{', '.join(sorted(names)[:4])}) beyond the "
                f"{declared.get(key, 0)} declared by the gather-ahead "
                "plan (fwd + AD-transpose pair each) — an undeclared "
                "all-gather materializes the full parameter on the step "
                "path",
                hint="add the param to the gather-ahead plan "
                     "(FLAGS_comm_overlap=tp_zero|all) or drop the "
                     "stray with_sharding_constraint")


# ---------------------------------------------------------------------------
# D-rules: donation / buffer lifetime
# ---------------------------------------------------------------------------

def _buf_base(name: str) -> str:
    return name.split("[", 1)[0]


def _buf_overlaps(a: str, b: str) -> bool:
    """"params" overlaps "params[3]" (whole-vs-block), exact indexes must
    match ("params[1]" does not overlap "params[2]")."""
    if _buf_base(a) != _buf_base(b):
        return False
    return a == b or "[" not in a or "[" not in b


@register_plan_rule(
    "D001", "read-after-donation", ERROR,
    "a sub-program reads a buffer an earlier sub-program donated (and "
    "nothing re-materialized it) — XLA may already have aliased the "
    "storage")
def _rule_read_after_donation(ctx: PlanContext):
    rule = _PLAN_RULES["D001"]
    donated: Dict[str, str] = {}  # buffer -> donor node
    for node in ctx.plan.nodes:
        for r in node.reads:
            for d, donor in donated.items():
                if _buf_overlaps(r, d):
                    yield _diag(
                        rule,
                        f"node {node.name!r} reads buffer {r!r} which "
                        f"{donor!r} already donated — the storage may be "
                        "aliased into that program's outputs",
                        hint="don't donate state a later sub-program "
                             "still consumes; reorder the dispatch or "
                             "drop the donation")
                    break
        # apply: donations poison, writes re-materialize
        for dn in node.donates:
            donated[dn] = node.name
        for w in node.writes:
            for d in [d for d in donated if _buf_overlaps(w, d)]:
                del donated[d]


@register_plan_rule(
    "D002", "double-donation", ERROR,
    "two sub-programs both donate the same buffer — the second donor "
    "hands XLA storage the first already reclaimed")
def _rule_double_donation(ctx: PlanContext):
    rule = _PLAN_RULES["D002"]
    donated: Dict[str, str] = {}
    for node in ctx.plan.nodes:
        for dn in node.donates:
            hit = next((donor for d, donor in donated.items()
                        if _buf_overlaps(dn, d)), None)
            if hit is not None:
                yield _diag(
                    rule,
                    f"buffer {dn!r} donated by {node.name!r} was already "
                    f"donated by {hit!r} with no intervening write — two "
                    "tiers claim the same storage",
                    hint="exactly one tier may own a buffer's lifetime; "
                         "the offload streamer and the compiled step must "
                         "not both donate it")
        for dn in node.donates:
            donated[dn] = node.name
        for w in node.writes:
            for d in [d for d in donated if _buf_overlaps(w, d)]:
                del donated[d]


@register_plan_rule(
    "D003", "broken-barrier-chain", ERROR,
    "the gather-ahead optimization_barrier chain is not total (a block "
    "missing its tie) or not acyclic (an edge against stream order), or "
    "was declared but never traced")
def _rule_barrier_chain(ctx: PlanContext):
    rule = _PLAN_RULES["D003"]
    g = ctx.plan.gather
    if g is None:
        return
    expected = set()
    for i, anch in enumerate(g.anchored):
        if anch and i >= g.depth and g.anchored[i - g.depth]:
            expected.add((i - g.depth, i))
    have = set(tuple(e) for e in g.edges)
    for a, b in sorted(have):
        if a >= b:
            yield _diag(
                rule,
                f"barrier edge ties block {b} before block {a} — the "
                "ordering chain is cyclic against the stream order",
                hint="edges must point forward: block i's gather is "
                     "ordered after block i-depth's")
    missing = expected - have
    for a, b in sorted(missing):
        yield _diag(
            rule,
            f"gather-ahead chain is not total: block {b} has no barrier "
            f"tie to block {a} (depth {g.depth}) — XLA is free to issue "
            "every gather at once, defeating the bounded prefetch window",
            hint="zero_gather_ahead must thread _ordered_after through "
                 "every anchored block")
    if ctx.facts is not None and expected and have and \
            ctx.facts.barriers == 0:
        yield _diag(
            rule,
            f"{len(have)} barrier edge(s) declared but the traced step "
            "contains no optimization_barrier equation — the chain is "
            "declared, not enforced",
            hint="the gathers must flow through overlap._ordered_after "
                 "inside the differentiated step")


@register_plan_rule(
    "D005", "cow-write-isolation", ERROR,
    "a sub-program writes or donates a buffer the plan declares "
    "copy-on-write-shared (flags['cow_shared_buffers']) — shared prefix "
    "pages are immutable; every write must target the private tail")
def _rule_cow_write_isolation(ctx: PlanContext):
    rule = _PLAN_RULES["D005"]
    declared = ctx.plan.flags.get("cow_shared_buffers")
    if not declared:
        return
    shared = {s.strip() for s in str(declared).split(",") if s.strip()}
    for node in ctx.plan.nodes:
        for attr in ("writes", "donates"):
            for buf in getattr(node, attr):
                hit = next((s for s in shared if _buf_overlaps(buf, s)),
                           None)
                if hit is not None:
                    yield _diag(
                        rule,
                        f"node {node.name!r} {attr} buffer {buf!r}, "
                        f"declared copy-on-write-shared ({hit!r}) — a "
                        "shared block must never be in a donated/"
                        "written set",
                        hint="route the write to the private page "
                             "region; shared prefix pages may only be "
                             "read (the engine also asserts this per "
                             "dispatch against the prefix tree's block "
                             "set)")
                    break


@register_plan_rule(
    "D004", "plan-capacity-exceeded", ERROR,
    "the composed tiers' static HBM plan (tools/hbm_budget.py) does not "
    "fit the chip budget at any candidate batch")
def _rule_capacity(ctx: PlanContext):
    cap = ctx.plan.capacity
    if cap is None:
        return
    for d in check_capacity(cap):
        yield d


def check_capacity(cap: Dict[str, Any], where: str = "") -> List[Diagnostic]:
    """D004 over one ``tools/hbm_budget.py`` plan dict."""
    rule = _PLAN_RULES["D004"]
    if cap.get("fits", True):
        return []
    d = _diag(
        rule,
        f"device-resident total {cap.get('device_gb', '?')} GB exceeds "
        f"the {cap.get('budget_gb', '?')} GB budget "
        f"(headroom {cap.get('headroom_gb', '?')} GB) for config "
        f"{cap.get('config', {})}",
        hint="enable FLAGS_offload_optimizer=moments, turn remat on, or "
             "shrink the batch (tools/hbm_budget.choose_batch)")
    if where:
        d.where = where
    return [d]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def check_plan(plan: StepPlan, closed_jaxpr=None, *,
               donate_argnums: Sequence[int] = (),
               rules: Optional[Sequence[str]] = None,
               where: str = "") -> List[Diagnostic]:
    """Run the S/D rules over one plan (+ optionally its traced jaxpr).
    Returns diagnostics sorted most-severe first; does not emit."""
    facts = collect_jaxpr_facts(closed_jaxpr) \
        if closed_jaxpr is not None else None
    ctx = PlanContext(plan, facts, tuple(donate_argnums))
    selected = all_plan_rules() if rules is None else \
        [_PLAN_RULES[r] for r in rules if r in _PLAN_RULES]
    out: List[Diagnostic] = []
    for rule in selected:
        try:
            out.extend(rule.fn(ctx) or ())
        except Exception as e:  # a broken rule must not kill the step path
            out.append(Diagnostic(
                rule=rule.rule_id, name=rule.name, severity="info",
                message=f"rule crashed: {type(e).__name__}: {e}"))
    for d in out:
        if where and not d.where:
            d.where = where
    out.sort(key=lambda d: -_SEV_ORDER.get(d.severity, 0))
    return out


def enforce(plan: StepPlan, closed_jaxpr=None, *,
            donate_argnums: Sequence[int] = (),
            where: str = "") -> List[Diagnostic]:
    """check_plan + route through the shared ``FLAGS_static_analysis``
    channel (off | warn | error), like the Pallas and comm checkers."""
    diags = check_plan(plan, closed_jaxpr, donate_argnums=donate_argnums,
                       where=where)
    if diags:
        emit(diags, where=where or "plan_check")
    return diags


# ---------------------------------------------------------------------------
# The tier-flag matrix (consumed by tools/lint_graph.py --matrix)
# ---------------------------------------------------------------------------

# The six flag-gated tiers and their supported values. Every combination
# is a supported composition; parts that cannot activate in a given
# environment (e.g. the decomposed TP matmul on a mesh whose mp axis is
# 1, or the multislice reduction on a mesh without a 'slice' axis)
# gate themselves off at the call site, and the plan records what was
# actually composed.
TIER_FLAGS: Tuple[Tuple[str, Tuple[Any, ...]], ...] = (
    ("offload_optimizer", ("off", "moments")),
    ("comm_overlap", ("off", "tp", "tp_zero", "all")),
    ("multislice", ("off", "hierarchical")),
    ("cp_nested_ring", (False, True)),
    ("pallas_conv", (0, 1)),
    ("remat", (False, True)),
)


def iter_tier_combos() -> Iterable[Dict[str, Any]]:
    """Every supported combination of the tier flags, stable order."""
    names = [n for n, _ in TIER_FLAGS]
    for values in itertools.product(*(v for _, v in TIER_FLAGS)):
        yield dict(zip(names, values))


_legacy_combo_warned = False


def normalize_combo(combo: Dict[str, Any]) -> Dict[str, Any]:
    """The ONE entry point every combo-dict consumer normalizes through
    (the matrix runner, the pass pipeline's plan-only builds, tests).

    Historically combos were 5-flag dicts (pre-multislice) and every
    consumer silently ``.get()``-defaulted the missing keys — a typo'd
    key or a stale caller then tested a different composition than it
    named. Now: unknown keys raise, missing keys fill with each tier's
    first (default) value with a once-per-process warning on the legacy
    shape, and the result always carries every ``TIER_FLAGS`` key in
    registry order."""
    global _legacy_combo_warned
    defaults = {n: vals[0] for n, vals in TIER_FLAGS}
    unknown = sorted(set(combo) - set(defaults))
    if unknown:
        raise ValueError(
            f"unknown tier-flag key(s) {unknown} in combo {combo!r}; "
            f"valid keys: {sorted(defaults)}")
    missing = [n for n in defaults if n not in combo]
    if missing and not _legacy_combo_warned:
        _legacy_combo_warned = True
        import warnings
        warnings.warn(
            f"legacy tier-flag combo dict missing {missing} "
            f"(pre-multislice 5-flag shape?); defaults filled — pass "
            f"every TIER_FLAGS key explicitly", stacklevel=2)
    return {n: combo.get(n, d) for n, d in defaults.items()}
