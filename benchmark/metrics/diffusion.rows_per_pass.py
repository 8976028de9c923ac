"""Rows a launched diffusion pass ran, on average:
``serving.diffusion_pass_rows`` (rows summed over the launches) over the
launches, which is what the ``serving.decode_step_ms`` histogram counted (one
observation a pass whose result was taken), whole process. A full batch reads
the cell's ``max_batch``; what is missing are rows between a request's end and
its successor's admission. A program that counts no such rows has nothing to
read: None."""
from benchmark.lib import program_spans as PS


def read(ctx):
    rows = PS.counter("serving.diffusion_pass_rows")
    took = PS.counter("serving.decode_step_ms")
    launches = took.get("count") if isinstance(took, dict) else None
    if rows is None or not launches:
        return None
    return {"value": rows / launches, "rows": rows, "launches": launches}
