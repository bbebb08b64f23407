"""idle_unattributed_share (%): the share of the device's idle time in
the traced window that the idle-gap breakdown charges to a span that
only contains other work (a round, a transfer, a batch codec call, the
fold) or to no span, rather than to a leaf step such as a copy, a
dispatch, a sync, staging, framing or training. It measures the
instrumentation's coverage, how much of the idle time the program's
tracing leaves unexplained, not the round's speed: any new leaf span
lowers it, and it moves ``round_s`` only through what it lets a reader
find. The breakdown is the harness's top-10 list, so gaps below its
cut-off are not counted."""

CONTAINERS = {
    "none", "round", "client.round_trip", "wire.transmit",
    "kernel.quantize_batch", "kernel.dequantize_batch",
    "kernel.dequant_accumulate8", "agg.accept_item", "agg.finish",
}


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= t["busy_s"]:
        return None
    unattributed = sum(s for label, s in t["idle_gaps"] if label in CONTAINERS)
    return 100.0 * unattributed / (t["window_s"] - t["busy_s"])
