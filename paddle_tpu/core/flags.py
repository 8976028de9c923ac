"""Global flag registry.

TPU-native re-design of the reference's gflags-style exported-flag system
(``paddle/phi/core/flags.cc`` — 98 exported flags; Python surface
``paddle.set_flags``/``get_flags`` at ``python/paddle/fluid/framework.py:7804``).

Flags are plain Python here (no C++ gflags): a typed registry seeded from
``FLAGS_*`` environment variables at import time, mutable at runtime via
``set_flags``.  Subsystems read flags lazily so runtime changes take effect.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

__all__ = [
    "define_flag",
    "get_flags",
    "set_flags",
    "flag",
    "watch",
    "unknown_env_flags",
]


@dataclass
class _FlagSpec:
    name: str
    default: Any
    type: type
    help: str
    on_change: Optional[Callable[[Any], None]] = None
    choices: Optional[tuple] = None


_registry: Dict[str, _FlagSpec] = {}
_values: Dict[str, Any] = {}
_lock = threading.RLock()


def _coerce(spec: _FlagSpec, value: Any) -> Any:
    if spec.type is bool and isinstance(value, str):
        value = value.lower() in ("1", "true", "yes", "on")
    value = spec.type(value)
    if spec.choices is not None and value not in spec.choices:
        raise ValueError(
            f"FLAGS_{spec.name}={value!r} is not a valid value; "
            f"choices: {list(spec.choices)}")
    return value


def _unknown_flag_error(name: str) -> KeyError:
    """KeyError naming the typo'd flag, the closest match, and the full
    valid-name list — a typo must never silently no-op."""
    import difflib
    close = difflib.get_close_matches(name, _registry, n=1)
    suggest = f" (did you mean {close[0]!r}?)" if close else ""
    return KeyError(
        f"Unknown flag {name!r}{suggest}; valid flags: {sorted(_registry)}")


def define_flag(name: str, default: Any, help: str = "",
                on_change: Optional[Callable[[Any], None]] = None,
                choices: Optional[Iterable[Any]] = None) -> None:
    """Register a flag. Environment variable ``FLAGS_<name>`` overrides default."""
    with _lock:
        spec = _FlagSpec(name=name, default=default, type=type(default),
                         help=help, on_change=on_change,
                         choices=tuple(choices) if choices else None)
        _registry[name] = spec
        env = os.environ.get("FLAGS_" + name)
        _values[name] = _coerce(spec, env) if env is not None else default


def flag(name: str) -> Any:
    """Read a single flag value (fast path used by subsystems)."""
    try:
        return _values[name]
    except KeyError:
        raise _unknown_flag_error(name) from None


def get_flags(names: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """Paddle-parity ``paddle.get_flags``: dict of flag values."""
    with _lock:
        if names is None:
            return dict(_values)
        if isinstance(names, str):
            names = [names]
        return {n: flag(n) for n in names}


def set_flags(flags_map: Dict[str, Any]) -> None:
    """Paddle-parity ``paddle.set_flags({'FLAGS_x': v})`` (prefix optional)."""
    with _lock:
        for name, value in flags_map.items():
            if name.startswith("FLAGS_"):
                name = name[len("FLAGS_"):]
            if name not in _registry:
                raise _unknown_flag_error(name)
            spec = _registry[name]
            _values[name] = _coerce(spec, value)
            if spec.on_change is not None:
                spec.on_change(_values[name])


def watch(name: str, fn: Callable[[Any], None]) -> None:
    """Call ``fn(value)`` now and after every later ``set_flags`` of
    ``name`` — for a hot path that keeps its own copy of a flag so as not
    to look it up by name on every call (``observability.trace``)."""
    with _lock:
        if name not in _registry:
            raise _unknown_flag_error(name)
        spec = _registry[name]
        prev = spec.on_change

        def both(value, _prev=prev, _fn=fn):
            if _prev is not None:
                _prev(value)
            _fn(value)

        spec.on_change = both
        fn(_values[name])


def list_flags() -> List[_FlagSpec]:
    with _lock:
        return list(_registry.values())


def unknown_env_flags() -> List[str]:
    """``FLAGS_*`` environment variables that match no registered flag —
    the set-time typo check extended to the env surface. Subsystems that
    define flags lazily (e.g. framework.determinism) should be imported
    before calling; the `tools/lint_graph.py` CLI reports these."""
    with _lock:
        return sorted(k for k in os.environ
                      if k.startswith("FLAGS_")
                      and k[len("FLAGS_"):] not in _registry)


# ---------------------------------------------------------------------------
# Built-in flags (subset of the reference's phi/core/flags.cc surface that is
# meaningful on TPU/XLA; allocator/cudnn flags have no TPU analog).
# ---------------------------------------------------------------------------

define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf during training steps "
            "(ref: FLAGS_check_nan_inf, phi/core/flags.cc).")
define_flag("check_nan_inf_level", 0,
            "0: error on NaN/Inf; higher levels only warn/log.")
define_flag("use_deterministic_reductions", False,
            "Force deterministic XLA reductions (bitwise reproducibility).")
define_flag("default_dtype", "float32", "Default floating point dtype.")
define_flag("jit_cache_size", 4096, "Max entries in the compiled-step cache.")
define_flag("log_level", 0, "Framework VLOG-style verbosity (0=off).")
define_flag("allocator_strategy", "xla",
            "Parity stub: memory is managed by XLA/PJRT on TPU.")
define_flag("embedding_deterministic", False,
            "Use deterministic (slower) embedding gradient scatter.")
define_flag("lockcheck", False,
            "Hand out instrumented locks (analysis.concurrency_check."
            "TrackedLock) that record real per-thread acquisition order "
            "for the T002 runtime cross-check. Off: plain threading "
            "locks, zero overhead.")
define_flag("flash_attn_version", 2, "Pallas flash-attention kernel version.")
define_flag("use_pallas_kernels", True,
            "Use Pallas TPU kernels where available (else jnp reference).")
define_flag("amp_dtype", "bfloat16", "Preferred mixed-precision compute dtype.")
define_flag("offload_optimizer", "off",
            "Optimizer-state memory tier (framework/offload.py): 'off' "
            "keeps all state in HBM (byte-identical to the pre-offload "
            "path); 'moments' parks first/second moments in pinned host "
            "memory and streams them through HBM per block during the "
            "update (ZeRO-Offload-style).",
            choices=("off", "moments"))
define_flag("telemetry", "metrics",
            "Runtime telemetry level (paddle_tpu.observability): 'off' "
            "disables every host-side signal (bitwise non-intrusive on "
            "step outputs), 'metrics' (default) keeps the always-on "
            "counters/gauges/histograms + step timeline + recompile "
            "sentinel + HBM watermarks + the boundary spans of the "
            "engine, the train step, compiles and collections in the "
            "in-memory ring, 'trace' adds the open-span table (hang "
            "post-mortems) and per-hop comm/* spans.",
            choices=("off", "metrics", "trace"))
define_flag("flight_recorder", "off",
            "Crash-persistent per-process flight recorder "
            "(paddle_tpu.observability.flight_recorder): 'off' (default) "
            "keeps every emit seam a no-op (byte-identical on step "
            "outputs, the FLAGS_telemetry contract); 'on' appends "
            "CRC-framed records (step phase commits, metric-snapshot "
            "deltas, O-rule diagnostics, guardian decisions, watchdog "
            "arm/fire, serving request outcomes, heartbeats, fired "
            "faults) into an mmap-backed ring that survives SIGKILL / "
            "os._exit with no flush — the input to observability.fleet "
            "and tools/postmortem.py.",
            choices=("off", "on"))
define_flag("fleet_telemetry", "off",
            "Live fleet telemetry exporter (paddle_tpu.observability."
            "live): 'off' (default) keeps every export seam a no-op "
            "(byte-identical on step outputs, the FLAGS_telemetry "
            "contract); 'on' runs a per-process daemon thread that "
            "every FLAGS_fleet_export_interval seconds publishes a "
            "CRC-framed, atomically-replaced snapshot of the metrics "
            "registry (plus step index / heartbeat / role.replica."
            "incarnation identity) under <run>/fleet/ — the input to "
            "the fleet aggregator, the SLO/alert rule engine "
            "(observability/alerts.py) and tools/fleet_top.py.",
            choices=("off", "on"))
define_flag("fleet_export_interval", 1.0,
            "Seconds between live fleet snapshot publications per "
            "worker (observability/live.py). Staleness classification "
            "keys off this: a worker whose latest snapshot is older "
            "than 2x its own advertised interval is 'dead'.")
define_flag("flight_recorder_mb", 4,
            "Flight-recorder ring capacity per process incarnation in "
            "MiB (the ring wraps — oldest records are overwritten).")
define_flag("static_analysis", "off",
            "Graph/kernel static analysis mode (paddle_tpu.analysis): "
            "'off' skips, 'warn' prints diagnostics to stderr, 'error' "
            "raises GraphLintError on error-severity findings.",
            choices=("off", "warn", "error"))
define_flag("comm_overlap", "off",
            "Communication-overlap tier (distributed/overlap.py): 'off' "
            "keeps every collective GSPMD-scheduled (byte-identical to "
            "the pre-overlap step); 'tp' decomposes the TP/SP "
            "all-gather->matmul and matmul->reduce-scatter into "
            "bidirectional ppermute pipelines; 'tp_zero' adds the ZeRO-3 "
            "param-gather-ahead prefetch; 'all' adds DP gradient-bucket "
            "overlap on the manual-sharding path.",
            choices=("off", "tp", "tp_zero", "all"))
define_flag("comm_overlap_chunks", 0,
            "Sub-chunk count per decomposed-matmul hop (scheduler "
            "interleave granularity); 0 consults the persistent "
            "autotune cache, else 1.")
define_flag("comm_overlap_bucket_mb", 25,
            "DP gradient bucket size in MiB for "
            "overlap.BucketedGradReducer (ref DataParallel "
            "comm_buffer_size default).")
define_flag("multislice", "off",
            "Multi-slice (cross-DCN) gradient-reduction tier "
            "(distributed/multislice): 'off' keeps the step on the "
            "single-mesh GSPMD path (byte-identical — also the behavior "
            "on meshes without a 'slice' axis); 'hierarchical' reduces "
            "dp grads intra-slice (ICI reduce-scatter) -> inter-slice "
            "(DCN allreduce on the 1/ici_size shard) -> intra-slice "
            "(ICI all-gather); 'flat' is the naive per-axis flat-psum "
            "baseline that moves the full bucket over DCN (bitwise "
            "identical values; comm_check C004 flags its plan) — kept "
            "as the measured A/B arm.",
            choices=("off", "flat", "hierarchical"))
define_flag("multislice_dcn_bucket_mb", 100,
            "DCN gradient bucket size in MiB for "
            "distributed/multislice.HierarchicalGradReducer — larger "
            "than FLAGS_comm_overlap_bucket_mb because the cross-slice "
            "latency floor (comm_check C005) is orders of magnitude "
            "above ICI's.")
define_flag("health_sentinel", "off",
            "Training-health step sentinel (fault/health.py): 'off' "
            "keeps the train step byte-identical; 'on' fuses one "
            "[loss, grad-global-norm] anomaly check into the compiled "
            "step (no host callbacks, no clean-path sync) and gates the "
            "optimizer update in-graph on finiteness + rolling-median "
            "spike/explosion thresholds, returning the stats vector for "
            "the host-side verdict (fault/guardian.py drives recovery).",
            choices=("off", "on"))
define_flag("serve_prefix_cache", False,
            "Radix prefix-sharing KV cache (serving/prefix_tree.py): "
            "requests whose prompts share a full-block prefix attach to "
            "the same immutable pages copy-on-write (refcounted "
            "BlockAllocator; only the partial tail block is private), "
            "eviction is LRU over refcount-0 trie leaves with a one-copy "
            "host spill tier. Off (default) keeps the engine "
            "byte-identical to the private-KV path.")
define_flag("serve_chunked_prefill", 0,
            "Chunked-prefill token budget for the serving engine: 0 "
            "(default) prefills every prompt in one bucketed dispatch "
            "(byte-identical to the pre-chunking engine); N > 0 splits "
            "prompts longer than N tokens into N-token chunks "
            "interleaved with the decode iterations so a long prompt "
            "no longer stalls resident decodes (N is rounded down to a "
            "multiple of the engine block size).")
define_flag("serve_speculative", 0,
            "Speculative-decoding draft depth (gamma) for the serving "
            "engine: 0 (default) decodes one token per iteration "
            "(byte-identical); N > 0 proposes N tokens per iteration "
            "from the drafter (NGramDrafter by default, or a "
            "ModelDrafter over a mirrored paged pool) and verifies them "
            "in ONE bucketed decode-gamma dispatch with the greedy "
            "accept-prefix rule; -1 consults the persistent autotune "
            "cache's accepted-length-derived gamma (falls back to 4).")
define_flag("cp_nested_ring", False,
            "Run the manual ring-attention CP path even when nested "
            "inside an enclosing manual shard_map (the pipeline "
            "runtime's pp axis) instead of falling back to "
            "GSPMD-scheduled attention. Exercised by the multichip "
            "dryrun's 4-axis scenario with loss parity against the "
            "fallback.")
