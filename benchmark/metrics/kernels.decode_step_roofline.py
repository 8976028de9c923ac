"""Share of its roofline that the decode program reaches: the least time to
read the weights once and the K and V of each resident row's REAL context
(from the step's own context lengths, not ``max_seq_len``) at the chip's
bandwidth, or its FLOPs at peak if that is longer, over the decode program's
device time. It counts the work, not what implements it, so it survives a
paged-attention kernel. What the step needs is the family's count
(``needs.decode_step_needs``)."""
from benchmark.lib import flops as F
from benchmark.lib.readers import decode_programs


def read(ctx):
    progs = decode_programs(ctx)
    if not progs:
        return None
    least = spent = flops = nbytes = 0.0
    for st, m in progs:
        f, b = ctx.family.needs.decode_step_needs(ctx.cfg, st["decode_ctx"])
        least += F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += m.dur
        flops += f
        nbytes += b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1]}
