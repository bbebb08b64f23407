"""d2h_gbps (GB/s): bytes of the program's ``host.d2h`` spans (the
``nbytes`` arg) over their summed duration: device arrays copied to the
host after the device finished them."""


def read(ctx):
    spans = [e for e in ctx.spans if e["name"] == "host.d2h"]
    nbytes = sum(e["args"].get("nbytes", 0) for e in spans)
    us = sum(e["dur"] for e in spans)
    if nbytes <= 0 or us <= 0:
        return None
    return nbytes / us / 1e3
