"""wire_encode_ms (ms/round): self time of the program's
``wire.encode_item`` spans per round (nested kernel and aggregator
spans excluded)."""
from tracereduce import self_time


def read(ctx):
    secs = self_time(ctx.spans, "wire.encode_item")
    if not ctx.rounds or secs <= 0:
        return None
    return 1000.0 * secs / ctx.rounds
