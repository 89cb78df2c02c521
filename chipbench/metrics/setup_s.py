"""Seconds from the start of the process to the start of the window."""


def reduce(ctx):
    return ctx.setup_s
