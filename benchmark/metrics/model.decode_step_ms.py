"""Mean device time of the engine's decode program per call (``XLA Modules``
line), over the traced steps."""
from benchmark.lib.readers import decode_programs, mean


def read(ctx):
    progs = decode_programs(ctx)
    return None if progs is None else mean(m.dur * 1e3 for _, m in progs)
