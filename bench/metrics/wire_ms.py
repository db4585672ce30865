"""Device self time of the comm streams (scope `fed.wire`: downlink
broadcast, uplink encode and decode, the curvature round trip, with
their repacks), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "wire")
