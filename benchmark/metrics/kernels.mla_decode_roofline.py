"""Share of its roofline that the absorbed latent (MLA) decode kernel
reaches: the least time the chip could take for one call a layer over each
row's REAL context (``needs.mla_decode_call_needs``: a latent row of 576
bf16 values a key read once, a score over 576 and a value sum over 512 a key
a head; the larger of the time its bytes take at the chip's bandwidth and
its FLOPs at peak) over the summed device time of the kernel's events
(``tpu_custom_call`` instructions named after the kernel,
``latent_paged_attention``) that fall inside the decode programs' intervals.
The other such calls of a decode program are XLA's own grouped products for
``ragged_dot`` (``ragged-dot-*``) and are not counted; prefill's flash calls
lie in prefill programs. ``calls_per_program`` should read the number of
layers. A family
without that count, or a program without such a call (the gather-and-dense
decode off the chip), has nothing to read: None."""
from benchmark.lib import flops as F
from benchmark.lib import trace as TR
from benchmark.lib.readers import decode_programs


KERNEL = "latent_paged_attention"


def read(ctx):
    needs = getattr(getattr(ctx.family, "needs", None),
                    "mla_decode_call_needs", None)
    progs = decode_programs(ctx)
    if needs is None or not progs or not ctx.trace.devices:
        return None
    ops = ctx.trace.devices[0].ops
    least = spent = flops = nbytes = 0.0
    calls = 0
    for st, m in progs:
        mine = [e for e in TR.within(ops, m.start, m.end)
                if TR.is_pallas_call(e.name)
                and KERNEL in TR.op_family(e.name)]
        if not mine:
            continue
        f, b = needs(ctx.cfg, st["decode_ctx"])
        least += len(mine) * F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += sum(e.dur for e in mine)
        calls += len(mine)
        flops += len(mine) * f
        nbytes += len(mine) * b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1],
            "calls": calls, "calls_per_program": calls / len(progs),
            "ms_per_call": 1e3 * spent / calls}
