"""The whole train step's share of the chips' bf16 peak: FLOPs the forward
and backward need per token (recompute not counted; the family's
``needs.train_flops_per_token``) times the tokens per second of the traced
stretch, over chips times peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    t = ctx.run.get("traced")
    if not t or not t["steps"]:
        return None
    tokens_per_s = t["steps"] * ctx.mix["batch"] * ctx.mix["seq"] / t["window_s"]
    need = ctx.family.needs.train_flops_per_token(
        ctx.cfg, ctx.mix["seq"]) * tokens_per_s
    return 100.0 * need / (ctx.chips * ctx.peaks.flops_bf16)
