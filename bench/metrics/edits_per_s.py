"""Edits acknowledged inside the window over its length (host clock)."""


def read(ctx):
    done = sum(1 for r in ctx.recs if r["ack"] <= ctx.t_end)
    return done / ctx.window_s
