"""Queries answered inside the window over the window's length."""


def read(ctx):
    done = sum(1 for r in ctx.requests if r.result is not None
               and ctx.t0 <= r.done <= ctx.t_close)
    return done / (ctx.t_close - ctx.t0)
