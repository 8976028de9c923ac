"""Seconds of ``jit/trace`` and ``jit/lower`` spans (jax tracing the Python,
lowering to MLIR; no cache covers them) before the window opened. The program
records a trace of 1 ms or more; the shorter ones are nearly all small
functions traced inside a larger trace, which the union counts once anyway."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.setup_seconds(ctx, ("jit/trace", "jit/lower"))
