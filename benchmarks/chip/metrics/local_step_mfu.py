"""local_step_mfu (%): forward + backward FLOPs of one local step, from
shapes, over the device time of one execution of the ``local_step``
program, against the chip's bf16 peak."""
from tracereduce import module_seconds


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, calls = module_seconds(ctx.trace, ("jit_local_step",))
    if not calls or secs <= 0:
        return None
    return 100.0 * calls * ctx.step_flops / (secs * ctx.peaks["bf16_flops"])
