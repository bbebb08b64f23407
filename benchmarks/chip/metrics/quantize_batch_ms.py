"""quantize_batch_ms (ms/round): host time in the program's
``kernel.quantize_batch`` spans per round of the window (device->host
copy, host concat, host->device copy and the kernel dispatches)."""
from tracereduce import span_totals


def read(ctx):
    secs = span_totals(ctx.spans, ["kernel.quantize_batch"])
    if not ctx.rounds or secs <= 0:
        return None
    return 1000.0 * secs / ctx.rounds
