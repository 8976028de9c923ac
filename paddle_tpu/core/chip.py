"""What the program knows about the chip it runs on, in one place: the
published peaks (one table, keyed by ``device_kind``) and where compiled
programs and tuned block sizes are kept between runs.

Nothing here touches a backend at import.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax

__all__ = ["ChipPeaks", "CHIP_PEAKS", "chip_peaks", "REPO_ROOT",
           "CACHE_ROOT", "compile_cache_dir", "enable_compile_cache",
           "autotune_cache_path"]


class ChipPeaks(NamedTuple):
    bf16_tflops: float   # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbs: float       # HBM bandwidth, GB/s per chip
    hbm_gb: float        # HBM capacity, GB per chip


# Keyed by ``jax.Device.device_kind`` exactly as the runtime reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A chip that is
# not listed is an error for every caller — add its row, with its source,
# before measuring on it.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_tflops=197.0, hbm_gbs=819.0, hbm_gb=16.0),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip named ``device_kind``; raises on a chip that is
    not in :data:`CHIP_PEAKS` (a default would put one chip's roofline
    under another chip's name)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks on record for device_kind "
            f"{device_kind!r}; known: {sorted(CHIP_PEAKS)} "
            "(paddle_tpu/core/chip.py CHIP_PEAKS)") from None


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# One fixed, git-ignored directory inside the checkout: the path is part
# of the compile cache's key, so it never carries a pid, a time or a
# tempfile name.
CACHE_ROOT = os.path.join(REPO_ROOT, ".cache")


def compile_cache_dir() -> str:
    """Where compiled programs are kept: ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself and no code sets another),
    otherwise ``<checkout>/.cache/jax`` — the same path on every call."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CACHE_ROOT, "jax"))


def enable_compile_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on for a process that
    compiles for the chip, and return its directory; called by the entry
    points before their first compile (this initialises the backend).

    Off the chip nothing is set and None is returned: on every load of a
    cached executable XLA:CPU logs a machine-feature mismatch ("could
    lead to ... SIGILL"), a CPU compile is seconds, and CPU runs are
    correctness runs that should not share compiled code with an earlier
    tree."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.default_backend() != "tpu":
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def autotune_cache_path() -> str:
    """Default file of the kernel autotune cache, beside the compile
    cache (block sizes are read at trace time, so they must not depend on
    a file outside the checkout)."""
    return os.path.join(CACHE_ROOT, "autotune.json")
