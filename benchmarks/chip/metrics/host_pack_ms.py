"""host_pack_ms (ms/round): host time in the program's ``host.pack``
spans per round: the wire codec's NumPy staging (the zeroed buffer that
joins a format group's tensors, and the joins of the sliced results)."""
from tracereduce import span_totals


def read(ctx):
    secs = span_totals(ctx.spans, ["host.pack"])
    if not ctx.rounds or secs <= 0:
        return None
    return 1000.0 * secs / ctx.rounds
