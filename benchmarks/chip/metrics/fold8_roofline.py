"""fold8_roofline (%): the bytes the window's int8 folds must move
(read the float32 sum, the codes and absmaxes; write the sum), at HBM
bandwidth, over the device time of ``dequant_accumulate8_into``."""
from flopcount import codec_bytes
from tracereduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, _ = module_seconds(ctx.trace, ("dequant_accumulate8_into",))
    nbytes = codec_bytes("fold8", ctx.kernel_elems.get("fold8", 0))
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
