"""Prompt tokens prefilled over the bucket lengths they were padded to
(``serving.prefill_tokens{kind=real}`` over ``{kind=bucket}``), whole
process."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.share(PS.counter("serving.prefill_tokens", kind="real"),
                    PS.counter("serving.prefill_tokens", kind="bucket"))
