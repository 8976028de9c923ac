"""Scheduling per ``serve/step``: the step's own time, ``serve/expire_shed``,
``serve/admit`` less its prefills, ``serve/ensure_blocks`` and
``serve/gauges``."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.per_step_ms(ctx, "sched")
