"""On the busiest device: time in which a collective runs and no other
operation does, over the step program's device time in the traced stretch.
Nothing to read on one chip."""
from benchmark.lib import trace as TR


def read(ctx):
    if ctx.trace is None or ctx.win is None or ctx.chips < 2:
        return None
    best = None
    for dev in ctx.trace.devices:
        _, durs = TR.main_program(dev, ctx.win)
        if not durs:
            continue
        busy = TR.total(TR.union(TR.clip(TR.ivs(dev.ops), *ctx.win)))
        if best is None or busy > best[0]:
            best = (busy, dev, sum(durs))
    if best is None:
        return None
    _, dev, step_s = best
    return 100.0 * TR.exposed_collective_seconds(dev, ctx.win) / step_s
