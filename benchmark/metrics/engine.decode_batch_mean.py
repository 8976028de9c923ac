"""Mean number of rows that gained a decode token per ``eng.step()`` that
decoded, over the whole window (the benchmark's own count)."""
from benchmark.lib.readers import mean


def read(ctx):
    return mean(len(s["decode_ctx"]) for s in ctx.run["steps"]
                if s["decode_ctx"])
