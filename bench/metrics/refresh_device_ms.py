"""Device busy time inside suggestion refreshes, per refresh, over the
traced window (ms)."""
import xplane as tr

from readers import REFRESH_SPAN, spans


def read(ctx):
    s = spans(ctx, REFRESH_SPAN)
    return tr.device_time_in(ctx.trace, s) / len(s) / 1e6 if s else None
