"""Time the host blocks on the device queue for one batched edit
dispatch's overflow flags, per dispatch in the window (BatchServer phase
counter, ms). Host time, not a device time: the wait covers whatever was
queued ahead of the flags, the stack copy, the edit step, and the previous
dispatch's unstack copies and re-ingest full forwards. None where the
program keeps no phase counters."""
from readers import ratio

NUM = "batch.sync_ns"


def read(ctx):
    if NUM not in ctx.after:
        return None
    return ratio(ctx, NUM, "batch.batch_steps", 1e-6)
