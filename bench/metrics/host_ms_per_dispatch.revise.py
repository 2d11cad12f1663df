"""Host time of one batched edit dispatch outside the device wait: the
round's takes, the stacking and uploads, the step's launch and the
adoption of its results, per dispatch in the window (BatchServer phase
counters, ms). None where the program keeps no phase counters."""
from readers import delta

PHASES = ("batch.take_ns", "batch.stack_ns", "batch.launch_ns",
          "batch.adopt_ns")


def read(ctx):
    if any(k not in ctx.after for k in PHASES):
        return None
    n = delta(ctx, "batch.batch_steps")
    return sum(delta(ctx, k) for k in PHASES) / n / 1e6 if n else None
