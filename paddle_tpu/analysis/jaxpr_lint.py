"""jaxpr-level program linter: static checks over traced programs.

The reference catches classes of training bugs at runtime with per-op C++
scans (``FLAGS_check_nan_inf``, graph passes over the ProgramDesc). The
XLA-idiomatic equivalent works one level earlier: any jitted step traces to
a jaxpr, and most of the expensive failure modes — accidental f64
promotion, host syncs compiled into a scan body, reused PRNG keys, dead
subgraphs, donation aliasing — are visible in that IR *before* compilation,
on any host, with no TPU attached.

Design: a recursive jaxpr walker feeds a pluggable rule registry; each rule
emits structured :class:`Diagnostic` records (rule id, severity, message,
eqn source location, fix hint). ``lint_fn`` traces a callable with
``jax.make_jaxpr`` and lints the result; :func:`emit` routes diagnostics
according to ``FLAGS_static_analysis`` (off | warn | error).

Rule catalog lives in ``paddle_tpu/analysis/RULES.md``.
"""

from __future__ import annotations

import sys
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import jax

from ._jaxpr_utils import (CALLBACK_PRIMS, INLINE_PRIMS, LOOP_PRIMS,
                           eqn_source, fmt_aval, inner_jaxprs)

__all__ = ["Diagnostic", "GraphLintError", "lint_jaxpr", "lint_fn",
           "register_rule", "all_rules", "emit", "analysis_mode",
           "ERROR", "WARNING", "INFO"]

ERROR = "error"
WARNING = "warning"
INFO = "info"

_SEV_ORDER = {INFO: 0, WARNING: 1, ERROR: 2}


@dataclass
class Diagnostic:
    """One structured finding — the shared currency of jaxpr lint, the
    Pallas checker, the AST repo lint, and the NaN/Inf runtime scans."""

    rule: str                 # stable id, e.g. "J001"
    name: str                 # human slug, e.g. "f64-promotion"
    severity: str             # error | warning | info
    message: str
    source: str = ""          # "file.py:123 (fn)" or "file.py:123"
    hint: str = ""
    where: str = ""           # surrounding context, e.g. "jit:train_step"

    def format(self) -> str:
        loc = f" at {self.source}" if self.source else ""
        ctx = f" [{self.where}]" if self.where else ""
        tail = f" — hint: {self.hint}" if self.hint else ""
        return (f"[{self.severity}] {self.rule}/{self.name}{ctx}: "
                f"{self.message}{loc}{tail}")

    def to_json(self) -> Dict[str, str]:
        """Machine-readable form (``tools/lint_graph.py --json``)."""
        return {"rule": self.rule, "name": self.name,
                "severity": self.severity, "message": self.message,
                "source": self.source, "hint": self.hint,
                "where": self.where}

    def __str__(self) -> str:
        return self.format()


class GraphLintError(RuntimeError):
    """Raised by :func:`emit` in error mode when error-severity
    diagnostics are present."""

    def __init__(self, diagnostics: Sequence[Diagnostic]):
        self.diagnostics = list(diagnostics)
        super().__init__(
            "static analysis found "
            f"{sum(1 for d in self.diagnostics if d.severity == ERROR)} "
            "error(s):\n" + "\n".join(d.format() for d in self.diagnostics))


# ---------------------------------------------------------------------------
# Walk context
# ---------------------------------------------------------------------------

@dataclass
class EqnInfo:
    eqn: Any
    loop_depth: int           # >0 inside a scan/while body
    jit_depth: int = 0        # >0 inside a pjit/shard_map compiled region


@dataclass
class LintContext:
    """Flattened view of one ClosedJaxpr handed to every rule."""

    closed_jaxpr: Any
    donate_argnums: tuple = ()
    eqns: List[EqnInfo] = field(default_factory=list)
    # var id -> number of consuming eqns (across all nesting levels)
    use_count: Dict[int, int] = field(default_factory=dict)
    # var id -> list of consuming EqnInfo
    consumers: Dict[int, List[EqnInfo]] = field(default_factory=dict)

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    def is_used(self, var) -> bool:
        return self.use_count.get(id(var), 0) > 0


# Primitives whose inner jaxpr executes as ONE compiled program: an eqn
# inside them is fused/scheduled by XLA; a collective OUTSIDE all of them
# (in a traced step that also contains such regions) is a one-off blocking
# dispatch on the step path (rule J014).
JIT_REGION_PRIMS = frozenset({"pjit", "jit", "xla_call", "shard_map"})


def _is_dropvar(v) -> bool:
    try:
        from jax._src.core import DropVar
        return isinstance(v, DropVar)
    except Exception:
        return type(v).__name__ == "DropVar"


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _build_context(closed_jaxpr, donate_argnums=()) -> LintContext:
    ctx = LintContext(closed_jaxpr, tuple(donate_argnums))

    def note_use(var, info):
        if _is_literal(var):
            return
        ctx.use_count[id(var)] = ctx.use_count.get(id(var), 0) + 1
        ctx.consumers.setdefault(id(var), []).append(info)

    # jax CACHES inner jaxprs: two identical pjit calls share one jaxpr
    # object (same eqn/var identities), so an unmemoized walk would double
    # every inner use count and fabricate "reused key" findings
    seen = set()

    def walk(jaxpr, loop_depth, jit_depth):
        key = (id(jaxpr), loop_depth > 0, jit_depth > 0)
        if key in seen:
            return
        seen.add(key)
        for eqn in jaxpr.eqns:
            info = EqnInfo(eqn, loop_depth, jit_depth)
            ctx.eqns.append(info)
            for v in eqn.invars:
                note_use(v, info)
            inner = inner_jaxprs(eqn)
            bump = 1 if eqn.primitive.name in LOOP_PRIMS else 0
            jbump = 1 if eqn.primitive.name in JIT_REGION_PRIMS else 0
            for _, closed in inner:
                walk(closed.jaxpr, loop_depth + bump, jit_depth + jbump)
        for v in jaxpr.outvars:
            if not _is_literal(v):
                ctx.use_count[id(v)] = ctx.use_count.get(id(v), 0) + 1

    walk(closed_jaxpr.jaxpr, 0, 0)
    return ctx


# ---------------------------------------------------------------------------
# Rule registry
# ---------------------------------------------------------------------------

@dataclass
class _Rule:
    rule_id: str
    name: str
    severity: str
    doc: str
    fn: Callable[[LintContext], Iterable[Diagnostic]]


_RULES: Dict[str, _Rule] = {}


def register_rule(rule_id: str, name: str, severity: str, doc: str):
    """Decorator: add ``fn(ctx) -> iterable[Diagnostic]`` to the registry.
    Project code can register extra rules; ``lint_jaxpr(rules=[...])``
    selects subsets by id."""

    def wrap(fn):
        _RULES[rule_id] = _Rule(rule_id, name, severity, doc, fn)
        return fn

    return wrap


def all_rules() -> List[_Rule]:
    return [_RULES[k] for k in sorted(_RULES)]


def _diag(rule: _Rule, message: str, eqn=None, hint: str = "",
          severity: Optional[str] = None) -> Diagnostic:
    return Diagnostic(rule=rule.rule_id, name=rule.name,
                      severity=severity or rule.severity, message=message,
                      source=eqn_source(eqn) if eqn is not None else "",
                      hint=hint)


# ---------------------------------------------------------------------------
# Seed rules (catalog: analysis/RULES.md)
# ---------------------------------------------------------------------------

_F64 = ("float64", "complex128")


@register_rule("J001", "f64-promotion", ERROR,
               "an equation creates a float64/complex128 value while the "
               "framework default dtype is float32")
def _rule_f64(ctx: LintContext):
    from ..core import flags
    try:
        if str(flags.flag("default_dtype")) not in ("float32", "bfloat16",
                                                    "float16"):
            return
    except KeyError:
        pass
    rule = _RULES["J001"]
    for info in ctx.eqns:
        eqn = info.eqn
        outs_f64 = [v for v in eqn.outvars
                    if hasattr(v, "aval") and hasattr(v.aval, "dtype")
                    and str(v.aval.dtype) in _F64]
        if not outs_f64:
            continue
        # flag the promotion POINT: inputs are not yet f64
        ins_f64 = any(hasattr(v, "aval") and hasattr(v.aval, "dtype")
                      and str(v.aval.dtype) in _F64 for v in eqn.invars)
        if ins_f64:
            continue
        yield _diag(
            rule,
            f"'{eqn.primitive.name}' produces {fmt_aval(outs_f64[0].aval)} "
            "— double precision is 2x memory and far slower on TPU",
            eqn,
            hint="cast explicitly to float32 (or set FLAGS_default_dtype) "
                 "— usually a numpy float64 scalar or np.array leaked in")


@register_rule("J002", "weak-scalar-arg", WARNING,
               "a Python scalar argument traced as a weak-typed 0-d value")
def _rule_weak_arg(ctx: LintContext):
    rule = _RULES["J002"]
    for i, v in enumerate(ctx.jaxpr.invars):
        aval = getattr(v, "aval", None)
        if aval is None or not getattr(aval, "weak_type", False):
            continue
        if getattr(aval, "ndim", None) != 0:
            continue
        yield _diag(
            rule,
            f"argument {i} is a weak-typed Python scalar "
            f"({fmt_aval(aval)}) — each distinct Python numeric type "
            "retraces, and its dtype follows promotion rules silently",
            hint="pass jnp.asarray(x, dtype=...) or mark it static")


@register_rule("J003", "captured-scalar-const", WARNING,
               "a 0-d scalar from the enclosing scope is baked into the "
               "graph as a constant")
def _rule_captured_scalar(ctx: LintContext):
    rule = _RULES["J003"]
    for var, val in zip(ctx.jaxpr.constvars, ctx.closed_jaxpr.consts):
        if getattr(val, "ndim", None) == 0 or isinstance(val, (int, float)):
            yield _diag(
                rule,
                f"scalar constant {val!r} captured from enclosing scope is "
                "baked into the compiled graph; a changed value is NOT "
                "picked up without retracing",
                hint="thread it through as an argument (or functools.partial "
                     "per configuration)")


@register_rule("J004", "dead-code", WARNING,
               "an effect-free equation whose outputs are never consumed")
def _rule_dead_code(ctx: LintContext):
    rule = _RULES["J004"]
    for info in ctx.eqns:
        eqn = info.eqn
        if eqn.primitive.name in CALLBACK_PRIMS:
            continue
        if getattr(eqn, "effects", None):
            continue
        # a fully-dead eqn traces with all-DropVar outputs; a live Var
        # with zero consumers is dead too (outvar of an inner jaxpr aside)
        outs = [v for v in eqn.outvars if not _is_dropvar(v)]
        if outs and any(ctx.is_used(v) for v in outs):
            continue
        aval = eqn.outvars[0].aval if eqn.outvars else None
        yield _diag(
            rule,
            f"result of '{eqn.primitive.name}' "
            f"({fmt_aval(aval) if aval is not None else '?'}) is never "
            "used — dead subgraph traced and compiled for nothing",
            eqn,
            hint="drop the computation or return/consume its value")


def _is_key_aval(aval) -> bool:
    try:
        import jax
        return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


@register_rule("J005", "prng-key-reuse", WARNING,
               "the same PRNG key feeds two or more random consumers")
def _rule_key_reuse(ctx: LintContext):
    rule = _RULES["J005"]
    seen_vars = set()
    seen_sources = set()  # one finding per user line: inlined pjit levels
    for info in ctx.eqns:  # replay the same reuse with fresh inner vars
        for v in info.eqn.invars:
            if _is_literal(v) or id(v) in seen_vars:
                continue
            aval = getattr(v, "aval", None)
            if aval is None:
                continue
            consumers = ctx.consumers.get(id(v), [])
            if len(consumers) < 2:
                continue
            # (a) a typed key var with >=2 consumers, or (b) a raw key
            # buffer wrapped twice (jax.random re-wraps uint32 key data
            # per call, so double use of an old-style key shows up here)
            wraps = [c for c in consumers
                     if c.eqn.primitive.name == "random_wrap"]
            if _is_key_aval(aval) or len(wraps) >= 2:
                seen_vars.add(id(v))
                src = eqn_source(consumers[-1].eqn)
                if src in seen_sources:
                    continue
                seen_sources.add(src)
                prims = sorted({c.eqn.primitive.name for c in consumers})
                yield _diag(
                    rule,
                    f"PRNG key consumed by {len(consumers)} equations "
                    f"({', '.join(prims)}) — reused keys give correlated "
                    "(identical) random streams",
                    consumers[-1].eqn,
                    hint="jax.random.split / fold_in before each use")


@register_rule("J006", "constant-prng-seed", WARNING,
               "a PRNG key is seeded from a compile-time constant")
def _rule_const_seed(ctx: LintContext):
    rule = _RULES["J006"]
    for info in ctx.eqns:
        eqn = info.eqn
        if eqn.primitive.name != "random_seed":
            continue
        if all(_is_literal(v) for v in eqn.invars):
            seedv = getattr(eqn.invars[0], "val", "?")
            yield _diag(
                rule,
                f"PRNGKey({seedv!r}) baked into the graph: every call "
                "replays the identical random stream",
                eqn,
                hint="derive the seed from program state (step counter, "
                     "core.random.next_key()) and pass it in")


@register_rule("J007", "callback-in-loop", ERROR,
               "a host callback inside a scan/while body syncs the host "
               "every iteration")
def _rule_callback_in_loop(ctx: LintContext):
    rule = _RULES["J007"]
    for info in ctx.eqns:
        if info.loop_depth > 0 and \
                info.eqn.primitive.name in CALLBACK_PRIMS:
            yield _diag(
                rule,
                f"'{info.eqn.primitive.name}' inside a compiled loop body "
                f"(depth {info.loop_depth}) — a device->host round-trip "
                "per iteration serializes the loop",
                info.eqn,
                hint="hoist the callback out of the loop, or accumulate "
                     "and report once per step")


@register_rule("J008", "host-callback", INFO,
               "a host callback compiled into the graph")
def _rule_callback(ctx: LintContext):
    rule = _RULES["J008"]
    for info in ctx.eqns:
        if info.loop_depth == 0 and \
                info.eqn.primitive.name in CALLBACK_PRIMS:
            yield _diag(
                rule,
                f"'{info.eqn.primitive.name}' forces a host sync when it "
                "runs (debug/check path?)",
                info.eqn,
                hint="fine for debugging; gate it off in production steps")


@register_rule("J009", "donated-passthrough", ERROR,
               "a donated input buffer is returned unchanged")
def _rule_donated(ctx: LintContext):
    rule = _RULES["J009"]
    if not ctx.donate_argnums:
        return
    out_ids = {id(v) for v in ctx.jaxpr.outvars}
    for i in ctx.donate_argnums:
        if i >= len(ctx.jaxpr.invars):
            continue
        v = ctx.jaxpr.invars[i]
        if id(v) in out_ids:
            yield _diag(
                rule,
                f"donated argument {i} ({fmt_aval(v.aval)}) flows to an "
                "output unchanged — XLA may alias the donated buffer and "
                "the caller's array is invalidated",
                hint="don't donate pass-through state, or copy it "
                     "(x + 0) before returning")


_INT32_MAX = 2 ** 31 - 1


@register_rule("J010", "gather-index-overflow", WARNING,
               "gather/scatter indices that can overflow their dtype")
def _rule_gather_overflow(ctx: LintContext):
    rule = _RULES["J010"]
    for info in ctx.eqns:
        eqn = info.eqn
        if eqn.primitive.name not in ("gather", "scatter", "scatter-add",
                                      "dynamic_slice", "dynamic_update_slice"):
            continue
        if len(eqn.invars) < 2:
            continue
        operand = eqn.invars[0]
        oaval = getattr(operand, "aval", None)
        if oaval is None or not hasattr(oaval, "shape"):
            continue
        nelem = 1
        for d in oaval.shape:
            nelem *= int(d)
        for idx in eqn.invars[1:]:
            iaval = getattr(idx, "aval", None)
            if iaval is None or not hasattr(iaval, "dtype"):
                continue
            dt = str(iaval.dtype)
            if not (dt.startswith("int") or dt.startswith("uint")):
                continue
            import numpy as np
            bits = np.dtype(dt).itemsize * 8
            if bits < 32:
                yield _diag(
                    rule,
                    f"'{eqn.primitive.name}' indexes "
                    f"{fmt_aval(oaval)} with {dt} indices — wraps past "
                    f"{2 ** (bits - 1) - 1} elements",
                    eqn, hint="cast indices to int32/int64")
                break
            if bits == 32 and nelem > _INT32_MAX:
                yield _diag(
                    rule,
                    f"'{eqn.primitive.name}' over {fmt_aval(oaval)} "
                    f"({nelem} elements) with int32 indices — flattened "
                    "offsets overflow int32",
                    eqn, severity=ERROR,
                    hint="use int64 indices or shard the table")
                break


@register_rule("J011", "nondeterministic-reduction", WARNING,
               "a reduction whose combining order is not fixed, under "
               "deterministic mode")
def _rule_nondet_reduction(ctx: LintContext):
    from ..core import flags
    det = False
    try:
        det = bool(flags.flag("use_deterministic_reductions"))
    except KeyError:
        pass
    if not det:
        try:
            from ..framework import determinism
            det = determinism.is_deterministic()
        except Exception:
            det = False
    if not det:
        return
    rule = _RULES["J011"]
    for info in ctx.eqns:
        name = info.eqn.primitive.name
        if name in ("scatter-add", "scatter_add", "scatter-mul"):
            yield _diag(
                rule,
                f"'{name}' accumulates colliding indices in hardware "
                "order — not bitwise reproducible across layouts, but "
                "deterministic mode is on (framework/determinism.py)",
                info.eqn,
                hint="set FLAGS_embedding_deterministic or use a sorted "
                     "segment-sum formulation")


def _transfer_kinds(eqn) -> List[str]:
    """Explicit memory-kind targets of a device_put eqn: a Sharding with
    a declared ``memory_kind``, or a ``jax.memory.Space`` (how a traced
    function names host/device memory without a concrete sharding)."""
    kinds = []
    for d in (eqn.params.get("devices") or ()):
        if isinstance(d, jax.memory.Space):
            if d is not jax.memory.Space.Any:
                kinds.append(d.name.lower())
            continue
        k = getattr(d, "memory_kind", None)
        if k is not None:
            kinds.append(str(k))
    return kinds


@register_rule("J012", "transfer-in-loop", ERROR,
               "a host<->device memory-kind transfer (device_put) compiled "
               "into a scan/while body")
def _rule_transfer_in_loop(ctx: LintContext):
    """The offload accident: a host-committed operand (e.g. a pinned-host
    moment buffer) consumed inside a compiled loop forces a device_put —
    a synchronous host<->device round trip EVERY iteration, serializing
    the loop on the host link. Correct offload streams at dispatch level
    with explicit prefetch (framework/offload.py StreamingUpdate); a
    memory-kind device_put belongs between compiled programs, not inside
    their loop bodies."""
    rule = _RULES["J012"]
    for info in ctx.eqns:
        if info.eqn.primitive.name != "device_put" or info.loop_depth == 0:
            continue
        kinds = _transfer_kinds(info.eqn)
        if not kinds:
            continue  # plain placement device_put, not a tier move
        yield _diag(
            rule,
            f"device_put to memory kind {kinds[0]!r} inside a compiled "
            f"loop body (depth {info.loop_depth}) — a host<->device "
            "transfer per iteration serializes the loop on the host link",
            info.eqn,
            hint="hoist the transfer out of the loop and stream per block "
                 "at dispatch level with explicit prefetch "
                 "(framework/offload.StreamingUpdate)")


@register_rule("J013", "telemetry-callback-in-step", WARNING,
               "a host callback compiled into a step graph while "
               "FLAGS_telemetry is not 'trace' — telemetry must stay "
               "host-side")
def _rule_telemetry_callback(ctx: LintContext):
    """Telemetry spans/metrics are host-side by design (observability/
    step_monitor times at dispatch level). A ``pure_callback``/
    ``io_callback``/``debug.print`` inside a jitted train step is the
    instrumented-the-wrong-layer accident: it forces a device->host sync
    per dispatch and under ``FLAGS_telemetry=off`` it still fires —
    exactly the non-intrusiveness guarantee the flag promises. Only an
    explicitly requested trace run (``FLAGS_telemetry=trace``) may accept
    in-graph callbacks as a temporary debugging aid."""
    from ..core import flags
    try:
        if str(flags.flag("telemetry")) == "trace":
            return
    except KeyError:
        pass
    rule = _RULES["J013"]
    prims = CALLBACK_PRIMS | {"debug_print"}
    for info in ctx.eqns:
        if info.eqn.primitive.name not in prims:
            continue
        yield _diag(
            rule,
            f"'{info.eqn.primitive.name}' compiled into the step graph "
            "while FLAGS_telemetry != 'trace' — a host sync per dispatch "
            "that no flag can turn off",
            info.eqn,
            hint="move the measurement to dispatch level "
                 "(observability.step_monitor phases / metrics), or run "
                 "under FLAGS_telemetry=trace while debugging")


_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_scatter", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "all_reduce", "pmax", "pmin",
})

# A psum below this operand size is a per-parameter reduction, not a
# bucket: ~1 MiB is well under any sane DP bucket (the reference's
# EagerReducer default is 25 MB).
_J014_BUCKET_BYTES = 1 << 20
# This many separate small reductions in one program = an unbucketed
# per-parameter chain.
_J014_CHAIN_MIN = 4


def _eqn_collectives(eqn) -> List[str]:
    """Collective primitive names inside an eqn's inner jaxprs (for
    spotting a shard_map that exists only to run one collective)."""
    names: List[str] = []
    stack = [closed.jaxpr for _, closed in inner_jaxprs(eqn)]
    while stack:
        j = stack.pop()
        for e in j.eqns:
            names.append(e.primitive.name)
            stack.extend(closed.jaxpr for _, closed in inner_jaxprs(e))
    return [n for n in names if n in _COLLECTIVE_PRIMS]


@register_rule("J014", "overlap-defeating-collectives", WARNING,
               "communication patterns the latency-hiding scheduler "
               "cannot overlap: per-parameter unbucketed reduce chains, "
               "and blocking collectives dispatched outside the compiled "
               "step")
def _rule_overlap_defeating(ctx: LintContext):
    """Two shapes of collective traffic that defeat overlap:

    (a) **Unbucketed per-parameter reduce chains** — many separate small
    ``psum``/``psum_scatter`` equations (one per parameter). Each is a
    latency-bound collective the scheduler cannot coalesce; the fix is
    size-bucketed reduction (``distributed.overlap.BucketedGradReducer``,
    the EagerReducer discipline).

    (b) **Blocking collectives outside jit on the step path** — a traced
    step that contains compiled regions (pjit) AND dispatches collectives
    outside them (a bare collective eqn, or a shard_map whose body is
    nothing but collectives — the eager collective-wrapper shape). Each
    such dispatch is its own XLA program: a host round-trip and a
    synchronization point per call, invisible to the scheduler that
    overlaps in-graph collectives.
    """
    rule = _RULES["J014"]

    # (a) per-parameter unbucketed reduce chains
    small: List[EqnInfo] = []
    small_bytes = 0
    for info in ctx.eqns:
        if info.eqn.primitive.name not in ("psum", "psum_scatter"):
            continue
        nbytes = 0
        for v in info.eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            n = 1
            for d in aval.shape:
                n *= int(d)
            nbytes += n * getattr(getattr(aval, "dtype", None),
                                  "itemsize", 4)
        if nbytes < _J014_BUCKET_BYTES:
            small.append(info)
            small_bytes += nbytes
    if len(small) >= _J014_CHAIN_MIN:
        yield _diag(
            rule,
            f"{len(small)} separate psum equations, each under "
            f"{_J014_BUCKET_BYTES // 1024} KiB "
            f"({small_bytes / 1024:.1f} KiB total) — a per-parameter "
            "reduce chain of latency-bound collectives the scheduler "
            "cannot overlap with backward compute",
            small[-1].eqn,
            hint="bucket the grads (distributed.overlap."
                 "BucketedGradReducer.reduce_in_axis): one flat psum per "
                 "~25 MB bucket overlaps with the remaining backward")

    # (b) blocking collectives outside jit on a step path
    has_compiled_region = any(
        i.jit_depth == 0 and i.eqn.primitive.name in ("pjit", "jit",
                                                      "xla_call")
        for i in ctx.eqns)
    if not has_compiled_region:
        return
    for info in ctx.eqns:
        if info.jit_depth > 0:
            continue
        name = info.eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            yield _diag(
                rule,
                f"collective '{name}' dispatched outside the compiled "
                "step (the step path also contains jitted regions) — a "
                "blocking one-off program per call",
                info.eqn,
                hint="move the collective inside the jitted step so XLA "
                     "schedules it, or bucket it "
                     "(distributed.overlap)")
        elif name == "shard_map":
            colls = _eqn_collectives(info.eqn)
            inner_total = 0
            for _, closed in inner_jaxprs(info.eqn):
                inner_total += len(closed.jaxpr.eqns)
            if colls and inner_total <= 2 * len(colls):
                yield _diag(
                    rule,
                    f"shard_map wrapping only collectives "
                    f"({', '.join(sorted(set(colls)))}) dispatched "
                    "outside the compiled step — an eager blocking "
                    "collective per call on the step path",
                    info.eqn,
                    hint="fuse it into the jitted step, or bucket the "
                         "transfers (distributed.overlap."
                         "BucketedGradReducer)")


def _collective_axes(eqn) -> List[str]:
    """Named mesh axes a collective equation operates over."""
    axes: List[str] = []
    for key in ("axis_name", "axes"):
        val = eqn.params.get(key)
        if val is None:
            continue
        for a in (val if isinstance(val, (tuple, list)) else (val,)):
            if isinstance(a, str):
                axes.append(a)
    return axes


@register_rule("J015", "dcn-collective-in-loop", WARNING,
               "a collective crossing a DCN-class mesh axis inside a "
               "scan/while body — a cross-slice round trip per iteration")
def _rule_dcn_collective_in_loop(ctx: LintContext):
    """Multi-slice discipline (distributed/multislice): only the once-
    per-step dp gradient reduction may cross the between-slice DCN; a
    collective over a dcn-class axis (comm_check.dcn_axes — 'slice' by
    default) inside a compiled loop body (a scan over layers, a decode
    inner loop) pays the ~tens-of-microseconds cross-slice RTT every
    iteration, serializing the loop on the slowest link in the system."""
    from . import comm_check
    dcn = comm_check.dcn_axes()
    if not dcn:
        return
    rule = _RULES["J015"]
    for info in ctx.eqns:
        if info.loop_depth == 0 or \
                info.eqn.primitive.name not in _COLLECTIVE_PRIMS:
            continue
        crossed = sorted(dcn.intersection(_collective_axes(info.eqn)))
        if not crossed:
            continue
        yield _diag(
            rule,
            f"'{info.eqn.primitive.name}' over DCN-class axis "
            f"{crossed[0]!r} inside a compiled loop body (depth "
            f"{info.loop_depth}) — a cross-slice DCN round trip per "
            "iteration",
            info.eqn,
            hint="hoist the collective out of the loop (reduce once per "
                 "step), or keep the inner loop's collectives on ICI "
                 "axes and reduce across slices hierarchically "
                 "(distributed/multislice.HierarchicalGradReducer)")


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def lint_jaxpr(closed_jaxpr, *, donate_argnums: Sequence[int] = (),
               rules: Optional[Sequence[str]] = None,
               where: str = "") -> List[Diagnostic]:
    """Lint one ClosedJaxpr. Returns diagnostics sorted most-severe first."""
    ctx = _build_context(closed_jaxpr, donate_argnums)
    selected = all_rules() if rules is None else \
        [_RULES[r] for r in rules if r in _RULES]
    out: List[Diagnostic] = []
    for rule in selected:
        try:
            out.extend(rule.fn(ctx) or ())
        except Exception as e:  # a broken rule must not kill the trace path
            out.append(Diagnostic(
                rule=rule.rule_id, name=rule.name, severity=INFO,
                message=f"rule crashed: {type(e).__name__}: {e}"))
    for d in out:
        if where and not d.where:
            d.where = where
    out.sort(key=lambda d: -_SEV_ORDER.get(d.severity, 0))
    return out


def lint_fn(fn: Callable, *args, donate_argnums: Sequence[int] = (),
            rules: Optional[Sequence[str]] = None, where: str = "",
            **kwargs) -> List[Diagnostic]:
    """Trace ``fn(*args, **kwargs)`` with ``jax.make_jaxpr`` and lint it."""
    import jax
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return lint_jaxpr(closed, donate_argnums=donate_argnums, rules=rules,
                      where=where or getattr(fn, "__name__", ""))


def analysis_mode() -> str:
    """Current ``FLAGS_static_analysis`` mode: off | warn | error."""
    from ..core import flags
    try:
        return str(flags.flag("static_analysis"))
    except KeyError:
        return "off"


def emit(diagnostics: Sequence[Diagnostic], where: str = "",
         mode: Optional[str] = None) -> List[Diagnostic]:
    """Route diagnostics per ``FLAGS_static_analysis``.

    off: return silently. warn: print every diagnostic to stderr (and
    ``warnings.warn`` the errors). error: raise :class:`GraphLintError`
    when any error-severity diagnostic is present, warn otherwise.
    """
    mode = mode or analysis_mode()
    if mode == "off" or not diagnostics:
        return list(diagnostics)
    for d in diagnostics:
        if where and not d.where:
            d.where = where
    errors = [d for d in diagnostics if d.severity == ERROR]
    if mode == "error" and errors:
        raise GraphLintError(list(diagnostics))
    for d in diagnostics:
        print(d.format(), file=sys.stderr)
    if errors:
        warnings.warn(
            f"static analysis: {len(errors)} error-severity finding(s) "
            f"in {where or 'graph'} (FLAGS_static_analysis=warn)",
            stacklevel=2)
    return list(diagnostics)
