"""KV positions the decoded rows needed over the positions the decode program
was handed (``serving.kv_tokens{kind=needed}`` over ``{kind=gathered}``:
context lengths over width x table x block), whole process."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.share(PS.counter("serving.kv_tokens", kind="needed"),
                    PS.counter("serving.kv_tokens", kind="gathered"))
