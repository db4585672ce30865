"""Device self time of the comm streams (scope `fed.wire`: downlink
broadcast, uplink encode and decode, the curvature round trip, with
their repacks), in ms per round."""


def read(ctx):
    return ctx.phase_ms("wire")
