"""Share of the decode programs' leaf-op device time, on the traced stretch,
that the program's name table puts under one of the engine's seam scopes
(``lib/device_names.py``: the ``jit_serve_decode`` and
``jit_serve_block_decode`` modules). Beside it, ms a program: ``by_scope``
(each seam scope), ``by_family`` (a family's scope under its seam),
``by_op`` (the largest op families in each seam), ``unmatched_ms`` (ops the
join did not find; ``unmatched_ops`` their largest op families),
``unnamed_ops`` (the largest op families with no
scope); ``programs``, ``leaf_ms`` (the leaf ops' sum) and ``program_ms``
(the module's span). A program that keeps no name table: None."""
from benchmark.lib import device_names as DN


def read(ctx):
    return DN.share(ctx, DN.DECODE)
