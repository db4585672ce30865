"""Roofline share of the Sophia update kernel, in percent: the bytes of
its operands and results at the HBM peak, over its summed device time
(memory bound: a few FLOPs per 20 bytes)."""


def read(ctx):
    return ctx.kernel_roofline("sophia_update")
