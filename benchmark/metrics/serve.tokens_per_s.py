"""All output tokens that became visible in the window over the window's
length (host clock around ``eng.step()``)."""


def read(ctx):
    return ctx.run["tokens_out"] / ctx.run["window_s"]
