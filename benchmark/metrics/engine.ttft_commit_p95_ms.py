"""95th percentile of submit -> commit of the first token
(``token_t_ns[0] - t_submit_ns``) of finished requests, first token in the
window: beside ``engine.ttft_p95_ms``, which waits for the step to return.
``second_token_gap_p50_ms`` is the gap to the next commit stamp."""
from benchmark.lib import program_spans as PS


def read(ctx):
    return PS.first_token(ctx)
