"""95th percentile of ALL gaps between consecutive output tokens of a request
whose later token fell in the window, on the benchmark's clock around
``eng.step()``."""
from benchmark.lib.readers import percentile


def read(ctx):
    return percentile(ctx.run["gaps_ms"], 95)
