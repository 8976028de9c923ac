"""Share of its roofline that the decode program's attention kernel reaches:
the least time the chip could take to read the K and V of each row's REAL
context in every layer (``sum(decode_ctx) * layers * 2 * hidden`` bf16 values
at the chip's bandwidth, or the ``4 * hidden`` FLOPs a key at peak if that is
longer: the work, not what the kernel happens to read) over the summed device
time of the ``tpu_custom_call`` events that fall inside the decode programs'
intervals. Prefill's flash calls lie in prefill programs and are not counted.
A program without such a call (the gather-and-dense decode) has nothing to
read: None. ``calls_per_program`` should read the number of layers.

The count is the K/V-page kernel's, at the width of a cache that keeps K and
V of ``hidden_size`` a token a layer, read from the configuration and not
from its family's ``needs``: ``tests/test_paged_attention.py`` (the kernel's
tests, outside the benchmark's paths) hands this reader a ``Ctx`` without a
family. A cell whose cache is grouped or latent is not listed here and
brings the reader of its own kernel (PERF.md, Open questions)."""
from benchmark.lib import flops as F
from benchmark.lib import trace as TR
from benchmark.lib.readers import decode_programs


def read(ctx):
    progs = decode_programs(ctx)
    if not progs or not ctx.trace.devices:
        return None
    ops = ctx.trace.devices[0].ops
    L, h = ctx.cfg["num_layers"], ctx.cfg["hidden_size"]
    least = spent = flops = nbytes = 0.0
    calls = 0
    for st, m in progs:
        mine = [e for e in TR.within(ops, m.start, m.end)
                if TR.is_pallas_call(e.name)]
        if not mine:
            continue
        keys = sum(st["decode_ctx"]) * L
        f, b = float(keys * 4 * h), float(keys * 2 * h * F.BF16)
        least += F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += sum(e.dur for e in mine)
        calls += len(mine)
        flops += f
        nbytes += b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1],
            "calls": calls, "calls_per_program": calls / len(progs),
            "ms_per_call": 1e3 * spent / calls}
