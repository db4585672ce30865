"""Device self time of the server's combine (scope `fed.combine`: the
mean of the decoded wires, the downlink correction and the model
update), in ms per round."""


def read(ctx):
    return ctx.phase_ms("combine")
