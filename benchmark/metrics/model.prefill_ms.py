"""Mean device time of a prefill program per call (both buckets together),
over the traced steps."""
from benchmark.lib.readers import mean, prefill_programs


def read(ctx):
    progs = prefill_programs(ctx)
    return None if progs is None else mean(m.dur * 1e3 for m in progs)
