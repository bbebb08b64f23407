"""stream_frame_ms (ms/round): self time of the program's
``stream.item`` spans per round (one item's chunk loop; on loopback the
receiver's reassembly runs inside it), less the spans of every other
layer nested in them on the same thread: decode, stages, kernels, the
fold, device and copies. Spans that enclose the item (``wire.transmit``)
are not its children."""
from collections import defaultdict

from tracereduce import union_length

CHILDREN = ("wire.", "stage.", "kernel.", "agg.", "dev.", "host.")


def read(ctx):
    by_tid = defaultdict(list)
    for e in ctx.spans:
        by_tid[e["tid"]].append(e)
    total_us = 0.0
    for evs in by_tid.values():
        kids = [(e["ts"], e["ts"] + e["dur"]) for e in evs
                if e["name"].startswith(CHILDREN)]
        for e in evs:
            if e["name"] != "stream.item":
                continue
            s, t = e["ts"], e["ts"] + e["dur"]
            inner, _ = union_length((a, b) for a, b in kids if s <= a and b <= t)
            total_us += e["dur"] - inner
    if not ctx.rounds or total_us <= 0:
        return None
    return total_us / 1e3 / ctx.rounds
