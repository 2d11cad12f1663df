"""Documents per batched edit dispatch in the window (BatchServer
counters)."""
from readers import ratio


def read(ctx):
    return ratio(ctx, "batch.batched_docs", "batch.batch_steps")
