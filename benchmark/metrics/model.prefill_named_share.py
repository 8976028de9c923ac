"""``model.decode_named_share`` for the prefill and extend programs (the
``jit_serve_prefill`` and ``jit_serve_extend`` modules), with the same
figures beside it."""
from benchmark.lib import device_names as DN


def read(ctx):
    return DN.share(ctx, DN.PREFILL)
