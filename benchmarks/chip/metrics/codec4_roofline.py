"""codec4_roofline (%): the bytes the nf4 quantize and dequantize calls
of the window must move, at HBM bandwidth, over the device time of the
programs that run them (``_pallas_q4_full``, ``_pallas_d4_full``)."""
from flopcount import codec_bytes
from tracereduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, _ = module_seconds(ctx.trace, ("pallas_q4_full", "pallas_d4_full"))
    nbytes = sum(codec_bytes(k, ctx.kernel_elems.get(k, 0)) for k in ("q4", "d4"))
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
