"""Share of its roofline that the block paged attention kernel reaches: the
least time the chip could take for one call a layer over each row's keys
(``needs.block_paged_call_needs``: a row's K and V read once, its block's
queries in and outputs out, a score and a value sum a key a query head a
position of the block; the larger of the time its bytes take at the chip's
bandwidth and its FLOPs at peak) over the summed device time of the kernel's
events (``tpu_custom_call`` instructions named after the kernel,
``block_paged_attention``) that fall inside the decode programs' intervals.
``calls_per_program`` should read the number of layers.

The step record does not list a pass's rows, so they are rebuilt from it.
The driver appends a row's gained tokens to ``decode_ctx`` as a run of
consecutive contexts, and a block's tokens become visible together, in the
step that took its commit pass's result: a row whose run ends at entry ``c``
in step ``t`` attended ``c + 1`` keys (its context and the block) in each of
the block's passes, ``generation.denoising_steps`` denoise passes and a
commit (five as published). A pass is launched in one step and its result
taken in the next, and the program that STARTS inside a step's span is the
one that step launched, so those passes are the programs of steps ``t - 5 ..
t - 1``. The last five traced steps, whose rows are not all known, are left
out, and so is a step whose programs do not match its record (one that
admitted: the driver books a prompt at its first visible token, not at its
prefill).

**Valid on the schedule branch of the unmask rule only.** Where the
confidence threshold unmasked any position
(``serving.diffusion_unmasked{rule=threshold}`` above 0), blocks take
different numbers of passes and the record does not say which took how many:
the rows cannot be rebuilt, and the reader gives None rather than a share
that could pass 100 %.

What this misses, all of it under 2 % of the keys: an answer's first and last
block take fewer than five passes; a first block with one visible token
leaves no entry (its first token is booked as the prompt's); the masked tail
of a last block is attended but not in ``c``; two rows whose runs happen to
be consecutive are split after every ``block_length`` entries.

A family without the count, or a program without such a call (the
gather-and-dense pass off the chip, or the parent commit), has nothing to
read: None."""
from benchmark.lib import flops as F
from benchmark.lib import program_spans as PS
from benchmark.lib import trace as TR

KERNEL = "block_paged_attention"


def read(ctx):
    family_needs = getattr(ctx.family, "needs", None)
    needs = getattr(family_needs, "block_paged_call_needs", None)
    if (needs is None or ctx.trace is None or ctx.win is None
            or "traced" not in ctx.run or not ctx.trace.devices
            or PS.counter("serving.diffusion_unmasked", rule="threshold")
            != 0):
        return None
    steps = ctx.run["traced"]["steps"]
    spans = TR.modules_in_spans(ctx.trace, "bench.engine_step", ctx.win)
    gen = ctx.cfg["generation"]
    passes = gen["denoising_steps"] + 1     # a whole block's, and its commit
    keys = [[] for _ in steps]          # of the pass each step launched
    for t, st in enumerate(steps):
        for c in family_needs.runs_end(st["decode_ctx"],
                                       gen["block_length"]):
            for u in range(max(0, t - passes), t):
                keys[u].append(c + 1)
    ops = ctx.trace.devices[0].ops
    least = spent = flops = nbytes = 0.0
    calls = progs = 0
    for u, ((_, mods), st) in enumerate(zip(spans, steps)):
        if u + passes >= len(steps) or not keys[u] or st["prefills"] \
                or len(mods) != 1:
            continue
        mine = [e for e in TR.within(ops, mods[0].start, mods[0].end)
                if TR.is_pallas_call(e.name)
                and KERNEL in TR.op_family(e.name)]
        if not mine:
            continue
        f, b = needs(ctx.cfg, keys[u])
        least += len(mine) * F.roofline_seconds(f, b, ctx.peaks)[0]
        spent += sum(e.dur for e in mine)
        calls += len(mine)
        progs += 1
        flops += len(mine) * f
        nbytes += len(mine) * b
    if spent <= 0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": F.roofline_seconds(flops, nbytes, ctx.peaks)[1],
            "calls": calls, "calls_per_program": calls / progs,
            "ms_per_call": 1e3 * spent / calls,
            "rows_per_call": sum(len(k) for k in keys) / max(len(keys), 1)}
