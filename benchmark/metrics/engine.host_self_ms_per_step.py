"""Mean per ``serve/step`` of its duration less every ``*/wait`` span inside
it: what the host adds to a step by the program's own spans, the inside twin of
``engine.host_ms_per_step``. Over the untraced part of the window;
``traced_value`` is the same over the traced stretch, ``by_span`` the five
parts that add up to it."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.per_step_ms(ctx, "host_self", by=PS.PARTS)
