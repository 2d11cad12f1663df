"""Set-up: process start to window start (host clock, s)."""


def read(ctx):
    return ctx.setup_s
