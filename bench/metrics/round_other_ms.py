"""Device busy time of the round outside every `fed.*` scope (the
local scan's carry and slicing, rng folding, the metrics outputs, and
any operation no scope covers), in ms per round: the busy time per
round less the six phases."""
from bench import tracing


def read(ctx):
    return ctx.phase_ms(tracing.REST)
