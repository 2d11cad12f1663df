"""p95 over every edit due in the window of due time to acknowledgement
(host clock, ms); a failed or unacknowledged edit is infinite."""
from readers import p95_ms


def read(ctx):
    return p95_ms(r["ack"] - r["due"] for r in ctx.recs)
