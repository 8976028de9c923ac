"""Process start to window open: imports, weights from the seed, compile or
cache read, the first three checked steps or the warm-up and pre-roll."""


def read(ctx):
    return ctx.run["setup_s"]
