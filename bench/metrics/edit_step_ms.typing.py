"""Device time of one execution of the batched edit-step program, mean
over the traced window (ms)."""
from readers import EDIT_STEP, program_ms


def read(ctx):
    return program_ms(ctx, EDIT_STEP)
