"""Kernel autotune harness with a persistent on-disk cache.

Reference parity: ``paddle/phi/kernels/autotune/cache.h:1`` (AlgorithmsCache
— runtime-measured algo choices keyed by shape/dtype, serialized across
runs) and ``switch_autotune.h`` (global enable switch). TPU-native form:
the tunable is a Pallas kernel's block configuration; measurement runs the
real kernel on-device eagerly (compile + time), and the winner is stored in
a JSON cache keyed by (kernel, chip, shape-key) that ``_pick_blocks``-style
selectors consult BEFORE their static tables. Autotuning happens at eager
level — under jit the cached (static) choice is read at trace time, which
is exactly when block sizes must be known.
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ...core import flags as _flags

__all__ = ["AutotuneCache", "get_cache", "autotune", "chip_kind"]

# Bumped when the measurement methodology changes; entries from older
# schemes are ignored (a wall-clock-era cache entry silently regressed the
# GPT bench by 22% in round 3 — never trust stale measurements).
CACHE_SCHEMA = 2

for _n, _d, _h in [
    ("kernel_autotune", 1, "consult the persistent kernel-autotune cache"),
    ("kernel_autotune_cache_path", "",
     "override the autotune cache file location"),
]:
    try:
        _flags.flag(_n)
    except KeyError:
        _flags.define_flag(_n, _d, _h)


def chip_kind() -> str:
    return jax.devices()[0].device_kind.replace(" ", "_")


def _default_path() -> str:
    p = str(_flags.flag("kernel_autotune_cache_path") or "")
    if p:
        return p
    p = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if p:
        return p
    from ...core.chip import autotune_cache_path
    return autotune_cache_path()


class AutotuneCache:
    """(kernel, chip, key) -> config, persisted as JSON (ref cache.h
    AlgorithmsCache + autotune_cache_utils serialization)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or _default_path()
        self._data: Dict[str, Any] = {}
        self._loaded = False

    def _key(self, kernel: str, key) -> str:
        return f"{kernel}|{chip_kind()}|{key}"

    def load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.path) as f:
                self._data = json.load(f)
        except (OSError, ValueError):
            self._data = {}

    def save(self):
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass  # cache is an optimization; never fail the program

    def get(self, kernel: str, key) -> Optional[Any]:
        if not _flags.flag("kernel_autotune"):
            return None
        self.load()
        ent = self._data.get(self._key(kernel, key))
        if not ent or ent.get("schema") != CACHE_SCHEMA:
            return None
        return ent["config"]

    def put(self, kernel: str, key, config, measured_ms: float):
        self.load()
        self._data[self._key(kernel, key)] = {
            "config": config,
            "measured_ms": round(measured_ms, 4),
            "tuned_at": time.strftime("%Y-%m-%d %H:%M:%S"),
            "schema": CACHE_SCHEMA,
        }
        self.save()

    def stats(self):
        self.load()
        return dict(self._data)


_cache: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    global _cache
    if _cache is None:
        _cache = AutotuneCache()
    return _cache


def _measure(run: Callable[[], Any], warmup: int, iters: int) -> float:
    """Measure DEVICE time of a kernel launch via a profiler trace: a
    single kernel is short beside the host's dispatch of it, so the host
    clock cannot rank block configs. Off the chip (interpret-mode runs,
    which have no device plane) it is the host clock around
    ``block_until_ready`` — a correctness harness, not a measurement."""
    import shutil
    import tempfile

    from ...profiler.statistic import device_total_ms

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(run())
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_autotune_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                r = run()
            jax.block_until_ready(r)
        dev_ms = device_total_ms(tmp)  # None off the chip, raises on a TPU
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if dev_ms is not None:
        return dev_ms / iters
    t0 = time.perf_counter()  # repo-lint: allow R001
    for _ in range(iters):
        r = run()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e3  # repo-lint: allow R001


def autotune(kernel: str, key, candidates: Sequence[Any],
             run_fn: Callable[[Any], Any], warmup: int = 1, iters: int = 3,
             measure: Optional[Callable[[Callable[[], Any]], float]] = None,
             cache: Optional[AutotuneCache] = None):
    """Sweep candidates on-device, persist and return the winner.

    run_fn(config) -> result (device arrays). A cached entry short-circuits
    the sweep. A candidate that raises (a block config the compiler
    refuses) loses the sweep; which were refused, and why, is warned once
    per sweep and named in the error when none ran."""
    c = cache or get_cache()
    hit = c.get(kernel, key)
    if hit is not None:
        return hit
    meas = measure or (lambda run: _measure(run, warmup, iters))
    best_cfg, best_ms = None, float("inf")
    refused: List[Tuple[Any, str]] = []
    for cfg in candidates:
        try:
            ms = meas(lambda: run_fn(cfg))
        except Exception as e:  # the compiler's reason is the finding
            refused.append((cfg, f"{type(e).__name__}: {str(e)[:200]}"))
            continue
        if ms < best_ms:
            best_cfg, best_ms = cfg, ms
    why = "; ".join(f"{cfg!r}: {msg}" for cfg, msg in refused)
    if best_cfg is None:
        raise ValueError(
            f"autotune({kernel}): no candidate ran for {key} — {why}")
    if refused:
        warnings.warn(f"autotune({kernel}, {key}): {len(refused)} of "
                      f"{len(candidates)} candidate(s) refused — {why}")
    c.put(kernel, key, best_cfg, best_ms)
    return best_cfg
