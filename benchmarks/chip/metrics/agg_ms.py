"""agg_ms (ms/round): host time in the program's ``agg.accept_item``
and ``agg.finish`` spans per round (the server fold)."""
from tracereduce import span_totals


def read(ctx):
    secs = span_totals(ctx.spans, ["agg.accept_item", "agg.finish"])
    if not ctx.rounds or secs <= 0:
        return None
    return 1000.0 * secs / ctx.rounds
