"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals / window), in percent."""


def read(ctx):
    red = ctx.reduction
    if red is None or red.window_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
