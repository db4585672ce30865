"""Device self time of the server's combine (scope `fed.combine`: the
mean of the decoded wires, the downlink correction and the model
update), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "combine")
