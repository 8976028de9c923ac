"""Share of its roofline that the gated-delta-rule decode kernel reaches: the
least time the chip could take for one call a linear layer over the decode
program's rows (``needs.gdn_decode_call_needs``: each row's float32 state
read once and written once, its q, k, v, g and beta in and its output out;
``7 d_k d_v`` FLOPs a head a row; the larger of the time its bytes take at
the chip's bandwidth and its FLOPs at peak) over the summed device time of
the kernel's events (``tpu_custom_call`` instructions named after the
kernel, ``gated_delta_decode``) that fall inside the decode programs'
intervals. The rows of a program are those of the step record it is paired
with (``readers.decode_programs``, as ``kernels.decode_step_roofline`` pairs
them). ``calls_per_program`` should read the number of linear layers.

A family without that count, or a program without such a call (the gather,
step and scatter off the chip, or the parent commit), has nothing to read:
None."""
from benchmark.lib import flops as F
from benchmark.lib import trace as TR
from benchmark.lib.readers import decode_programs

KERNEL = "gated_delta_decode"


def read(ctx):
    needs = getattr(getattr(ctx.family, "needs", None),
                    "gdn_decode_call_needs", None)
    progs = decode_programs(ctx)
    if needs is None or not progs or not ctx.trace.devices:
        return None
    ops = ctx.trace.devices[0].ops
    least = spent = flops = nbytes = 0.0
    calls = with_calls = 0
    for st, m in progs:
        mine = [e for e in TR.within(ops, m.start, m.end)
                if TR.is_pallas_call(e.name)
                and KERNEL in TR.op_family(e.name)]
        if not mine:
            continue
        f, b = needs(ctx.cfg, len(st["decode_ctx"]))
        least += len(mine) * F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += sum(e.dur for e in mine)
        calls += len(mine)
        with_calls += 1
        flops += len(mine) * f
        nbytes += len(mine) * b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1],
            "calls": calls, "calls_per_program": calls / with_calls,
            "ms_per_call": 1e3 * spent / calls}
