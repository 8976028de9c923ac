"""Passes a committed block took, a row a pass:
``serving.diffusion_passes{kind=denoise}`` + ``{kind=commit}`` over
``serving.diffusion_blocks``, whole process. On the schedule branch of the
unmask rule (one position a pass) a whole block of 4 reads 4 + 1 = 5.0; an
answer's first and last block have fewer masked positions and read a little
less, and a pass that unmasks several positions by the confidence threshold
less again. Beside it the share of the unmasked positions that the threshold
branch chose. A program that counts no such passes has nothing to read:
None."""
from benchmark.lib import program_spans as PS


def read(ctx):
    denoise = PS.counter("serving.diffusion_passes", kind="denoise")
    commit = PS.counter("serving.diffusion_passes", kind="commit")
    blocks = PS.counter("serving.diffusion_blocks")
    if denoise is None or commit is None or not blocks:
        return None
    out = {"value": (denoise + commit) / blocks, "denoise": denoise,
           "commit": commit, "blocks": blocks}
    by_thr = PS.counter("serving.diffusion_unmasked", rule="threshold")
    by_sched = PS.counter("serving.diffusion_unmasked", rule="schedule")
    if by_thr is not None and by_sched is not None and by_thr + by_sched:
        out["threshold_share"] = by_thr / (by_thr + by_sched)
    return out
