"""Seconds of ``jit/compile`` spans (backend compile, or the read from the
persistent cache) before the window opened; ``compiles`` and ``cache_hits``
are the program's counters."""
from benchmark.lib import program_spans as PS


def read(ctx):
    got = PS.setup_seconds(ctx, ("jit/compile",))
    if got is not None:
        got["compiles"] = PS.counter("jit.compiles")
        got["cache_hits"] = PS.counter("jit.cache_hits") or 0
    return got
