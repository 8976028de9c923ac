"""Device ms a decode program spends under the seam scopes ``head`` (the
final norm and the logits' product) and ``sample`` (the argmax, or a block
model's unmask rule), from the program's name table
(``lib/device_names.py``); ``by_op`` the op families in those two scopes,
ms a program. A program that keeps no name table: None."""
from benchmark.lib import device_names as DN


def read(ctx):
    got = DN.named_time(ctx, DN.DECODE)
    if got is None:
        return None
    ops = {}
    for seam in DN.HEAD:
        for fam, s in got["by_op"].get(seam, {}).items():
            ops[fam] = ops.get(fam, 0.0) + s
    n = got["n"]
    return {"value": 1e3 * sum(got["by_scope"].get(s, 0.0)
                               for s in DN.HEAD) / n,
            "by_op": DN.per_program_ms(ops, n), "programs": n}
