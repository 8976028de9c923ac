"""Wall time of an ``eng.step()`` less the device-busy time inside it, mean
over the traced steps: what the host adds to every inter-token gap."""
from benchmark.lib import trace as TR
from benchmark.lib.readers import mean


def read(ctx):
    if ctx.trace is None or ctx.win is None or not ctx.trace.devices:
        return None
    ops = ctx.trace.devices[0].ops
    host = []
    for s in ctx.trace.spans:
        if s.name == "bench.engine_step" and ctx.win[0] <= s.start < ctx.win[1]:
            # the engine waits for what it launches: a step's ops start in it
            inside = TR.ivs(TR.within(ops, s.start, s.end))
            busy = TR.total(TR.union(TR.clip(inside, s.start, s.end)))
            host.append((s.dur - busy) * 1e3)
    return mean(host)
