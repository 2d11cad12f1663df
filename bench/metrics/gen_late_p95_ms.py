"""How late the load generator sent: p95 of send time minus due time
over the window's edits (host clock, ms)."""
from readers import p95_ms


def read(ctx):
    if ctx.loop != "open":
        return None
    return p95_ms(r["sent"] - r["due"] for r in ctx.recs)
