"""copy_ms (ms/round): host time in the program's ``host.h2d`` and
``host.d2h`` spans per round: the dispatches that carry NumPy arguments
to the device, and the device->host copies, each timed alone (a
``dev.sync`` span waits for the device before every ``host.d2h``)."""
from tracereduce import span_totals


def read(ctx):
    secs = span_totals(ctx.spans, ["host.h2d", "host.d2h"])
    if not ctx.rounds or secs <= 0:
        return None
    return 1000.0 * secs / ctx.rounds
