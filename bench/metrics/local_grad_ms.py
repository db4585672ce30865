"""Device self time of the local forward and backward passes (scope
`fed.grad`: each local step's loss/grad boundary, unpack and pack
included), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "grad")
