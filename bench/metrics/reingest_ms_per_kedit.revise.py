"""Host time of the full-forward slow paths (overflow re-ingests, defrags
and grows) per 1,000 applied edits in the window (BatchServer phase
counter, ms): the host's admission, uploads and launch of each full
forward. Host time, not a device time: the full forwards run on the device
after the phase ends, and the next dispatch's device wait takes them in.
None where the program keeps no phase counters."""
from readers import ratio

NUM = "batch.reingest_ns"


def read(ctx):
    if NUM not in ctx.after:
        return None
    return ratio(ctx, NUM, "batch.edits_applied", 1000.0 * 1e-6)
