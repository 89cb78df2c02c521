"""Samples whose results came back in the window, over the window's
seconds (host clock)."""


def reduce(ctx):
    return ctx.samples / ctx.window_s if ctx.window_s > 0 else None
