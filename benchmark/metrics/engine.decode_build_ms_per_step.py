"""``serve/decode/build`` + ``serve/decode/launch`` per ``serve/step`` (with
the decode span's own time between its children): batch pick, filling tokens,
tables and lengths, the host-to-device copies, the call of the program."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.per_step_ms(ctx, "decode_build")
