"""Edits the front end admitted per scheduling round in the window
(AsyncBatchServer counters)."""
from readers import ratio


def read(ctx):
    return ratio(ctx, "async.admitted_edits", "async.rounds")
