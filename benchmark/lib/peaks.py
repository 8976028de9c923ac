"""Published peaks of the chips the benchmark may run on: the benchmark's own
copy (the program's table in ``paddle_tpu/core/chip.py`` may change; the
yardstick may not).

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float   # FLOP/s, dense bf16 matmul, one chip
    hbm_bytes_s: float  # bytes/s, one chip
    hbm_bytes: float    # bytes, one chip


# keyed by ``jax.Device.device_kind`` exactly as the runtime reports it
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_of(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a chip that is not in the table is an error,
    never a default (one chip's roofline under another chip's name)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (benchmark/lib/peaks.py)") from None
