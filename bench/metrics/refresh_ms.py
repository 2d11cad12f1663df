"""Wall time of one suggestion refresh, mean over the traced window
(benchmark spans around SuggestionEngine.refresh, ms)."""
from readers import REFRESH_SPAN, spans


def read(ctx):
    s = spans(ctx, REFRESH_SPAN)
    return sum(x.dur for x in s) / len(s) / 1e6 if s else None
