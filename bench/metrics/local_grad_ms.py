"""Device self time of the local forward and backward passes (scope
`fed.grad`: each local step's loss/grad boundary, unpack and pack
included), in ms per round."""


def read(ctx):
    return ctx.phase_ms("grad")
