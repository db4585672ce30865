"""Device self time of the Sophia update (scope `fed.sophia`: the
kernel launch and the relayouts around it), in ms per round."""


def read(ctx):
    return ctx.phase_ms("sophia")
