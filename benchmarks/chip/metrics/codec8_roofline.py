"""codec8_roofline (%): the bytes the blockwise8 quantize and dequantize
calls of the window must move, at HBM bandwidth, over the device time
of the programs that run them (``_pallas_q8_full``, ``_pallas_d8_full``)."""
from flopcount import codec_bytes
from tracereduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, _ = module_seconds(ctx.trace, ("pallas_q8_full", "pallas_d8_full"))
    nbytes = sum(codec_bytes(k, ctx.kernel_elems.get(k, 0)) for k in ("q8", "d8"))
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
