"""round_mfu (%): forward + backward FLOPs of the window's local steps,
from shapes, over the traced window times the chip's bf16 peak."""


def read(ctx):
    t = ctx.trace
    if not t or not ctx.steps or not ctx.peaks or t["window_s"] <= 0:
        return None
    return 100.0 * ctx.steps * ctx.step_flops / (t["window_s"] * ctx.peaks["bf16_flops"])
