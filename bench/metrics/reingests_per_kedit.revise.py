"""Full-forward re-ingests (overflow fallbacks and defrags) per 1,000
applied edits in the window (BatchServer counters)."""
from readers import ratio


def read(ctx):
    return ratio(ctx, "batch.full_forwards", "batch.edits_applied", 1000.0)
