"""``serve/decode/commit`` per ``serve/step``: pool swap, the commit of every
row's token, finishing (journal, detokenize, request record)."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.per_step_ms(ctx, "decode_commit")
