"""``serve/decode/checks`` per ``serve/step``: lint hook, copy-on-write
assertion of every row, recompile sentinel."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.per_step_ms(ctx, "decode_checks")
