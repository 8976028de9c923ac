"""Compiled-HLO verifier: cross-check declared plans against what XLA
actually built.

Every other analyzer in the stack verifies the *traced jaxpr* — what we
asked for. This module verifies the *optimized HLO* of the compiled
executable — what XLA/GSPMD actually emitted — against the same declared
:class:`~.plan_check.StepPlan`, on a CPU mesh, chipless:

- **X001** a collective op kind in the compiled HLO (all-reduce /
  all-gather / reduce-scatter / collective-permute / all-to-all) that
  nothing in the declared plan justifies — the GSPMD-inserted resharding
  gather the jaxpr never shows;
- **X002** a declared donation not realized as an input/output alias —
  the silent 2x HBM footgun (the donated buffer lives on next to its
  copy);
- **X003** compiled peak memory (``memory_analysis()``) exceeding the
  ``tools/hbm_budget.py`` envelope the plan carries (tolerance-gated);
- **X004** dtype churn the source never asked for: f64 values compiled
  while x64 is off, or convert round-trip chains (a->b->a) on the hot
  path;
- **X005** a DCN-class collective (replica groups crossing a
  ``comm_check.dcn_axes()`` mesh axis) inside a compiled while-loop
  body — the HLO-level analog of the jaxpr linter's J015.

Justification for X001 comes from the plan itself: a multi-axis mesh
justifies the reduction class (all-reduce / reduce-scatter — grad and
loss reductions are implicit in data-parallel training), sharded params
or a gather-ahead plan justify the gather class (all-gather /
collective-permute — GSPMD moves shards to use sites), and every
declared CommSpec justifies the op kinds its decomposition lowers to.
``all-to-all`` is never implicit. A plan with no mesh (the serving
engine's single-partition executables) justifies nothing: any collective
in its compiled HLO is a finding.

Wired as the final stage of ``sharded.TrainStep._maybe_lint`` and the
serving engine's first-dispatch lint (both under
``FLAGS_static_analysis``); ``tools/lint_graph.py --hlo`` runs it
standalone and the ``--matrix`` sweep runs it per tier-flag combination.
Rule catalog: ``analysis/RULES.md``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

from . import _hlo_utils
from ._hlo_utils import COLLECTIVE_OPS, HloModule
from .jaxpr_lint import Diagnostic, ERROR, WARNING, _SEV_ORDER, emit

__all__ = [
    "HloFacts", "collect_hlo_facts", "check_hlo", "enforce",
    "register_hlo_rule", "all_hlo_rules", "expected_collective_kinds",
    "SPEC_KINDS", "PEAK_TOLERANCE",
]

# Compiled peak may exceed the static envelope by this factor before
# X003 fires (runtime pads, fragmentation slack — same spirit as the
# O002 watermark slack).
PEAK_TOLERANCE = 0.10

# What each declared CommSpec's decomposition lowers to in optimized
# HLO: the ppermute pipelines become collective-permute chains; the
# hierarchical reduction stages keep their collective kind. An unknown
# spec name justifies every kind except all-to-all (permissive — a new
# tier should not fire X001 until its mapping lands here).
SPEC_KINDS: Dict[str, frozenset] = {
    "allgather_matmul": frozenset({"collective-permute"}),
    "matmul_reduce_scatter": frozenset({"collective-permute"}),
    "cp_ring": frozenset({"collective-permute"}),
    "slice_reduce_scatter": frozenset({"reduce-scatter"}),
    "dcn_allreduce": frozenset({"all-reduce"}),
    "slice_all_gather": frozenset({"all-gather"}),
}

_REDUCTION_KINDS = frozenset({"all-reduce", "reduce-scatter"})
_GATHER_KINDS = frozenset({"all-gather", "collective-permute"})
_PERMISSIVE_KINDS = COLLECTIVE_OPS - frozenset({"all-to-all"})


# ---------------------------------------------------------------------------
# Facts: what the compiled executable actually contains
# ---------------------------------------------------------------------------

@dataclass
class HloFacts:
    """The compiled executable, reduced to what the X-rules consume."""

    # collective op kind -> instruction count (async halves folded)
    collectives: Dict[str, int] = field(default_factory=dict)
    # collective instrs inside while bodies: (kind, groups-or-None)
    loop_collectives: List[Tuple[str, Optional[List[List[int]]]]] = \
        field(default_factory=list)
    # replica groups per kind (for DCN classification)
    groups: Dict[str, List[List[List[int]]]] = field(default_factory=dict)
    # (param_number, param_index) entries of input_output_alias
    aliases: List[Tuple[int, str]] = field(default_factory=list)
    # memory_analysis() byte dict + derived peak_bytes (None on backends
    # that do not report it)
    memory: Optional[Dict[str, int]] = None
    f64_values: int = 0
    convert_chains: int = 0
    n_instructions: int = 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "collectives": dict(self.collectives),
            "loop_collectives": len(self.loop_collectives),
            "aliases": len(self.aliases),
            "peak_bytes": (self.memory or {}).get("peak_bytes"),
            "f64_values": self.f64_values,
            "convert_chains": self.convert_chains,
            "instructions": self.n_instructions,
        }


def collect_hlo_facts(compiled) -> HloFacts:
    """Parse one compiled executable (or raw optimized-HLO text) into
    :class:`HloFacts`."""
    if isinstance(compiled, str):
        text, memory = compiled, None
    else:
        text = _hlo_utils.hlo_text(compiled)
        memory = _hlo_utils.memory_stats(compiled)
    mod = _hlo_utils.parse_hlo(text)
    facts = HloFacts(memory=memory, aliases=list(mod.aliases))
    # convert name -> (out dtype, operand name); operand dtypes come from
    # the instruction table (this XLA prints operands without their types)
    converts: Dict[str, Tuple[str, str]] = {}
    dtype_of: Dict[str, str] = {}
    import re as _re
    conv_pat = _re.compile(r"convert\((?:[^%()]*\s)?%([\w.\-]+)\)")
    for ins in mod.instructions():
        facts.n_instructions += 1
        dtype_of[ins.name] = ins.dtype
        if ins.dtype in ("f64", "c128"):
            facts.f64_values += 1
        if ins.op in COLLECTIVE_OPS:
            facts.collectives[ins.op] = facts.collectives.get(ins.op, 0) + 1
            facts.groups.setdefault(ins.op, []).append(ins.groups or [])
            if ins.computation in mod.loop_computations:
                facts.loop_collectives.append((ins.op, ins.groups))
        elif ins.op == "convert":
            m = conv_pat.search(ins.line)
            if m:
                converts[ins.name] = (ins.dtype, m.group(1))
    # convert round-trip chains: convert(convert(x: a) -> b) -> a — pure
    # churn (a->b->c staged casts are legitimate and not counted)
    for out_dtype, src_name in converts.values():
        inner = converts.get(src_name)
        if (inner is not None and out_dtype
                and dtype_of.get(inner[1]) == out_dtype):
            facts.convert_chains += 1
    return facts


# ---------------------------------------------------------------------------
# Rule registry (X family)
# ---------------------------------------------------------------------------

@dataclass
class HloContext:
    plan: Any                       # plan_check.StepPlan
    facts: HloFacts
    donated_leaves: int = 0
    capacity: Optional[Dict[str, Any]] = None


@dataclass
class _HloRule:
    rule_id: str
    name: str
    severity: str
    doc: str
    fn: Callable[[HloContext], Iterable[Diagnostic]]


_HLO_RULES: Dict[str, _HloRule] = {}


def register_hlo_rule(rule_id: str, name: str, severity: str, doc: str):
    def wrap(fn):
        _HLO_RULES[rule_id] = _HloRule(rule_id, name, severity, doc, fn)
        return fn

    return wrap


def all_hlo_rules() -> List[_HloRule]:
    return [_HLO_RULES[k] for k in sorted(_HLO_RULES)]


def _diag(rule: _HloRule, message: str, hint: str = "",
          severity: Optional[str] = None) -> Diagnostic:
    return Diagnostic(rule=rule.rule_id, name=rule.name,
                      severity=severity or rule.severity,
                      message=message, hint=hint)


# ---------------------------------------------------------------------------
# X001 — undeclared compiled collective
# ---------------------------------------------------------------------------

def _spec_axes(spec) -> set:
    used = set()
    for e in (tuple(spec) if spec is not None else ()):
        if e is None:
            continue
        used.update(e if isinstance(e, tuple) else (e,))
    return used


def expected_collective_kinds(plan) -> set:
    """Collective op kinds the declared plan justifies in compiled HLO."""
    exp: set = set()
    multi = any(int(v) > 1 for v in (plan.mesh_axes or {}).values())
    if multi:
        # grad/loss reductions and TP partial sums are implicit in any
        # multi-axis data-parallel step
        exp |= _REDUCTION_KINDS
        sharded = (plan.fsdp_axis is not None or plan.gather is not None
                   or any(_spec_axes(getattr(info, "spec", None))
                          for info in (plan.params or {}).values()))
        if sharded:
            # GSPMD moves declared shards to their use sites
            exp |= _GATHER_KINDS
    for _, spec in (plan.comm_specs or []):
        exp |= SPEC_KINDS.get(getattr(spec, "name", ""), _PERMISSIVE_KINDS)
    return exp


@register_hlo_rule(
    "X001", "undeclared-compiled-collective", ERROR,
    "a collective op kind in the compiled HLO that nothing in the "
    "declared plan justifies — GSPMD-inserted resharding the traced "
    "jaxpr never shows (S001 cannot see it)")
def _rule_undeclared_compiled_collective(ctx: HloContext):
    rule = _HLO_RULES["X001"]
    present = {k for k, n in ctx.facts.collectives.items() if n > 0}
    if not present:
        return
    expected = expected_collective_kinds(ctx.plan)
    for kind in sorted(present - expected):
        n = ctx.facts.collectives[kind]
        yield _diag(
            rule,
            f"{n} {kind} op(s) in the compiled HLO but the declared plan "
            "justifies none (no CommSpec maps to it"
            + ("" if expected
               else " and the plan declares no multi-device mesh at all")
            + ") — XLA/GSPMD inserted communication the jaxpr-level "
            "rules never saw",
            hint="declare the hop plan (comm_check.CommSpec) at the call "
                 "site, shard the consuming op so GSPMD stops resharding, "
                 "or — if the movement is intended — extend the plan's "
                 "comm_specs so the ICI/DCN accounting sees it")


# ---------------------------------------------------------------------------
# X002 — declared donation not realized
# ---------------------------------------------------------------------------

@register_hlo_rule(
    "X002", "donation-not-realized", ERROR,
    "a declared donation produced no input/output alias in the compiled "
    "module — XLA kept the donated buffer alive next to its copy (the "
    "silent 2x HBM footgun)")
def _rule_donation_not_realized(ctx: HloContext):
    rule = _HLO_RULES["X002"]
    donated = int(ctx.donated_leaves)
    if donated <= 0:
        return
    realized = len({a[0] for a in ctx.facts.aliases})
    if realized == 0:
        yield _diag(
            rule,
            f"the step declares {donated} donated buffer(s) but the "
            "compiled module's input_output_alias table is empty — no "
            "donation was realized; every donated input is double-"
            "buffered",
            hint="donated inputs alias only when an output matches their "
                 "shape/dtype/sharding — check that the updated state is "
                 "returned with the same sharding it came in with")
    elif realized < donated:
        yield _diag(
            rule,
            f"only {realized} of {donated} donated buffer(s) realized an "
            "input/output alias — the rest are double-buffered",
            hint="compare the step's in/out shardings; a dtype or layout "
                 "change on the update path breaks the alias",
            severity=WARNING)


# ---------------------------------------------------------------------------
# X003 — compiled peak exceeds the static HBM envelope
# ---------------------------------------------------------------------------

@register_hlo_rule(
    "X003", "compiled-peak-exceeds-plan", ERROR,
    "the compiled executable's peak memory (memory_analysis) exceeds "
    "the static tools/hbm_budget.py envelope the plan was verified "
    "against — the plan is missing a row (tolerance-gated)")
def _rule_compiled_peak(ctx: HloContext):
    rule = _HLO_RULES["X003"]
    cap = ctx.capacity or getattr(ctx.plan, "capacity", None)
    mem = ctx.facts.memory
    if not cap or mem is None:
        return
    budget_gb = cap.get("budget_gb")
    if not budget_gb:
        return
    peak = mem.get("peak_bytes", 0)
    envelope = float(budget_gb) * (1.0 + PEAK_TOLERANCE) * 2**30
    if peak > envelope:
        yield _diag(
            rule,
            f"compiled peak {peak / 2**30:.2f} GB exceeds the "
            f"{budget_gb} GB static envelope "
            f"(+{PEAK_TOLERANCE:.0%} tolerance) — args "
            f"{mem.get('argument_size_in_bytes', 0) / 2**30:.2f} GB, "
            f"temps {mem.get('temp_size_in_bytes', 0) / 2**30:.2f} GB",
            hint="the hbm_budget plan is missing a resident row (XLA "
                 "temp buffers, un-aliased outputs) — reconcile the plan "
                 "or shrink the batch (tools/hbm_budget.choose_batch)")


# ---------------------------------------------------------------------------
# X004 — dtype churn
# ---------------------------------------------------------------------------

@register_hlo_rule(
    "X004", "compiled-dtype-churn", ERROR,
    "dtype churn in the compiled module: f64/c128 values while x64 is "
    "off (2x memory, catastrophic on TPU), or convert round-trip "
    "chains (a->b->a) XLA kept on the hot path")
def _rule_dtype_churn(ctx: HloContext):
    rule = _HLO_RULES["X004"]
    if ctx.facts.f64_values:
        x64 = False
        try:
            import jax
            x64 = bool(jax.config.jax_enable_x64)
        except Exception:
            pass
        if not x64:
            yield _diag(
                rule,
                f"{ctx.facts.f64_values} f64/c128 value(s) in the "
                "compiled HLO while the default dtype is f32 — a leaked "
                "wide dtype survived to the executable",
                hint="find the source with the jaxpr linter's J001 (it "
                     "fires on the traced eqn); a python float or numpy "
                     "f64 scalar is the usual culprit")
    if ctx.facts.convert_chains:
        yield _diag(
            rule,
            f"{ctx.facts.convert_chains} convert round-trip chain(s) "
            "(a->b->a) in the compiled module — precision is destroyed "
            "and both converts execute on the hot path",
            hint="keep the value in the narrow dtype end to end, or drop "
                 "the intermediate cast; feeds the quantization tier's "
                 "dtype-accounting (ROADMAP item 5)",
            severity=WARNING)


# ---------------------------------------------------------------------------
# X005 — DCN-class collective in a compiled loop body
# ---------------------------------------------------------------------------

def _mesh_coords(plan) -> Optional[Tuple[Tuple[str, int], ...]]:
    axes = tuple((str(a), int(n)) for a, n in (plan.mesh_axes or {}).items())
    if not axes or any(n <= 0 for _, n in axes):
        return None
    return axes


def _crosses_dcn(group: List[int], axes, dcn_names) -> bool:
    """Does one replica group span distinct coordinates on any DCN-class
    mesh axis? Device ids are flat row-major over the plan's axis order
    (mesh.devices.flatten())."""
    total = 1
    for _, n in axes:
        total *= n
    if any(d >= total or d < 0 for d in group):
        return False  # unknown id layout: don't guess
    seen = set()
    for d in group:
        coords = []
        rem = d
        for name, n in reversed(axes):
            if name in dcn_names:
                coords.append(rem % n)
            rem //= n
        seen.add(tuple(coords))
    return len(seen) > 1


@register_hlo_rule(
    "X005", "dcn-collective-in-compiled-loop", WARNING,
    "a collective whose replica groups cross a DCN-class mesh axis "
    "sits inside a compiled while-loop body — the cross-slice RTT is "
    "paid every iteration (the HLO-level analog of J015)")
def _rule_dcn_collective_in_loop(ctx: HloContext):
    rule = _HLO_RULES["X005"]
    if not ctx.facts.loop_collectives:
        return
    axes = _mesh_coords(ctx.plan)
    if axes is None:
        return
    from . import comm_check
    dcn_names = comm_check.dcn_axes() & {a for a, _ in axes}
    if not dcn_names:
        return
    for kind, groups in ctx.facts.loop_collectives:
        if not groups:
            continue  # no printed topology: cannot classify
        crossing = [g for g in groups
                    if _crosses_dcn(g, axes, dcn_names)]
        if crossing:
            yield _diag(
                rule,
                f"a {kind} inside a compiled while-loop body has replica "
                f"groups crossing the DCN-class axis/axes "
                f"{sorted(dcn_names)} (e.g. group {crossing[0]}) — the "
                "cross-slice RTT is paid every loop iteration",
                hint="hoist the cross-slice reduction out of the loop "
                     "(the hierarchical dp reduction crosses DCN once "
                     "per step, distributed/multislice)")


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def check_hlo(plan, compiled, *, donated_leaves: int = 0,
              capacity: Optional[Dict[str, Any]] = None,
              rules: Optional[Sequence[str]] = None,
              where: str = "") -> List[Diagnostic]:
    """Run the X-rules over one compiled executable (or pre-collected
    :class:`HloFacts`, or raw HLO text) against its declared plan.
    Returns diagnostics sorted most-severe first; does not emit."""
    facts = compiled if isinstance(compiled, HloFacts) \
        else collect_hlo_facts(compiled)
    ctx = HloContext(plan, facts, int(donated_leaves), capacity)
    selected = all_hlo_rules() if rules is None else \
        [_HLO_RULES[r] for r in rules if r in _HLO_RULES]
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


def enforce(plan, compiled, *, donated_leaves: int = 0,
            capacity: Optional[Dict[str, Any]] = None,
            where: str = "") -> List[Diagnostic]:
    """check_hlo + route through the shared ``FLAGS_static_analysis``
    channel (off | warn | error), like every other checker."""
    diags = check_hlo(plan, compiled, donated_leaves=donated_leaves,
                      capacity=capacity, where=where)
    if diags:
        emit(diags, where=where or "hlo_check")
    return diags
