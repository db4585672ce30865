"""Model FLOP/s utilization of the traced rounds, in percent: the model
FLOPs of their local steps and curvature refreshes (bench/work.py,
counted from shapes) over the traced window's seconds and the chip's
bf16 peak."""


def read(ctx):
    red = ctx.reduction
    if red is None or red.window_ns <= 0:
        return None
    return 100.0 * ctx.flops / (red.window_ns * 1e-9) / ctx.peaks[
        "bf16_flops_per_s"]
