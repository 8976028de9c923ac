"""Share of their roofline that the flash-attention kernels reach: the least
time the chip could take for each executed call's FLOPs and bytes (from the
call's shapes; the causal mask's unmasked half only) over the summed device
time of those calls. The calls are the ``tpu_custom_call`` instructions whose
first operand is bf16[batch*heads, seq, head_dim]; the kind shows in what a
call returns: (o, lse) forward, one tensor dq, two tensors dk and dv."""
import re

from benchmark.lib import flops as F
from benchmark.lib import trace as TR

_SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def kind_and_shape(text):
    head, _, args = text.partition(" custom-call(")
    m = _SHAPE.search(args)
    if not m:
        return None
    outs = _SHAPE.findall(head)
    kind = "bwd_dkv" if len(outs) == 2 else (
        "fwd" if "f32[" in head else "bwd_dq")
    return kind, tuple(int(x) for x in m.groups())


def read(ctx):
    if ctx.trace is None or ctx.win is None or not ctx.trace.devices:
        return None
    least = spent = flops = nbytes = 0.0
    for e in ctx.trace.devices[0].ops:
        if not (ctx.win[0] <= e.start < ctx.win[1]
                and TR.is_pallas_call(e.name)):
            continue
        ks = kind_and_shape(e.name)
        if ks is None:
            continue
        kind, (bh, seq, hd) = ks
        f, b = F.flash_call_needs(kind, bh, seq, hd)
        least += F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += e.dur
        flops += f
        nbytes += b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1]}
