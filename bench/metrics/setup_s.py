"""Seconds from process start to the window: data, load, compile, warm-up."""


def read(ctx):
    return ctx.setup_s
