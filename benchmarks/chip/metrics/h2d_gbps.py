"""h2d_gbps (GB/s): bytes of the program's ``host.h2d`` spans (the
``nbytes`` arg) over their summed duration. Each span is a codec or
fold dispatch that carries NumPy arguments to the device, as the
untraced program makes it, so the rate is the bytes over the host's
hold of those dispatches: a copy the runtime finishes after the
dispatch returns is not in the time, and the rate then reads above
the link's."""


def read(ctx):
    spans = [e for e in ctx.spans if e["name"] == "host.h2d"]
    nbytes = sum(e["args"].get("nbytes", 0) for e in spans)
    us = sum(e["dur"] for e in spans)
    if nbytes <= 0 or us <= 0:
        return None
    return nbytes / us / 1e3
