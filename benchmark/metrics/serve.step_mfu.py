"""The serving window's share of the chip's bf16 peak: FLOPs needed for every
prompt token prefilled and every output token decoded in the window (each at
its real context; the family's ``needs.serve_flops``) over window times
peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    steps = ctx.run["steps"]
    if not steps:
        return None
    need = ctx.family.needs.serve_flops(
        ctx.cfg, ((p, 0) for s in steps for p in s["prefills"]),
        (c for s in steps for c in s["decode_ctx"]))
    return 100.0 * need / (ctx.run["window_s"] * ctx.chips
                           * ctx.peaks.flops_bf16)
