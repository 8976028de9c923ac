"""All tokens of all optimizer steps completed in the window over the
window's length (host clock; the last step closed by ``block_until_ready``);
on several chips the sum over the chips."""


def read(ctx):
    return ctx.run["tokens"] / ctx.run["window_s"]
