"""Mean device time of the train-step program per call: the program that
took most device time in the traced stretch, on the first device."""
from benchmark.lib import trace as TR
from benchmark.lib.readers import mean


def read(ctx):
    if ctx.trace is None or ctx.win is None or not ctx.trace.devices:
        return None
    _, durs = TR.main_program(ctx.trace.devices[0], ctx.win)
    return mean(d * 1e3 for d in durs)
