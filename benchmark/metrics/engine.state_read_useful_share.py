"""Rows of the decode programs that held a sequence over the rows whose state
slot the program read (``serving.state_rows{kind=needed}`` over
``{kind=read}``: the state kernel skips a pad row's null slot, the dense path
gathers every row's), whole process: the twin of
``engine.kv_read_useful_share`` for the other kind of cache. A program
without the counter (no layer keeps a state, or the parent commit) gives
nothing to read: None."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.share(PS.counter("serving.state_rows", kind="needed"),
                    PS.counter("serving.state_rows", kind="read"))
