"""Roofline share of the int8 wire kernels (downlink broadcast and
uplink round trip), in percent: the bytes of their operands and results
at the HBM peak, over their summed device time."""


def read(ctx):
    return ctx.kernel_roofline("quantize")
