"""Wait of an admitted edit from its admission to the start of its round's
flush, mean over the window (AsyncBatchServer counters, ms). None where
the program keeps no phase counters."""
from readers import ratio

NUM = "async.queue_wait_ns"


def read(ctx):
    if NUM not in ctx.after:
        return None
    return ratio(ctx, NUM, "async.admitted_edits", 1e-6)
