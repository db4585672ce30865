"""Device self time of the Sophia update (scope `fed.sophia`: the
kernel launch and the relayouts around it), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "sophia")
