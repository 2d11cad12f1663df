"""p95 over every edit due in the window of due time to the first
suggestion delivered on its document's stream that reflects it (host
clock, ms); an edit with no such suggestion is infinite."""
from readers import p95_ms


def read(ctx):
    return p95_ms(r["sugg"] - r["due"] for r in ctx.recs if "sugg" in r)
