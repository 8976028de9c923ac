"""95th percentile of due -> first token over the whole window, on the
benchmark's clock (a prefill stalls every resident row, so it moves the
gap tail)."""
from benchmark.lib.readers import percentile


def read(ctx):
    return percentile(ctx.run["ttft_ms"], 95)
