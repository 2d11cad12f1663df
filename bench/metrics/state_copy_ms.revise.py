"""Device time of the whole-state copies around the batched edit step, the
``stack_states`` and ``unstack_state`` programs, in the traced window per
execution of the edit-step program (ms). None where the program runs no
such programs by these names."""
import re

import xplane as tr

from readers import EDIT_STEP

COPIES = re.compile(r"jit_(stack_states|unstack_state)\b")


def read(ctx):
    if ctx.trace is None:
        return None
    runs = [e for evs in ctx.trace.modules.values()
            for e in tr.in_window(ctx.trace, evs)]
    copies = [e for e in runs if COPIES.search(e.name)]
    steps = [e for e in runs if re.search(EDIT_STEP, e.name)]
    if not copies or not steps:
        return None
    return sum(e.dur for e in copies) / len(steps) / 1e6
