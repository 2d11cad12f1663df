"""Share of the window's batched edit dispatches that were launched while
an earlier dispatch of the same scheduling step was still unsynced
(BatchServer counters, %). None where the program keeps no such counter."""
from readers import ratio


def read(ctx):
    if "batch.overlapped_dispatches" not in ctx.after:
        return None
    return ratio(ctx, "batch.overlapped_dispatches", "batch.batch_steps",
                 100.0)
