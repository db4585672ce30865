"""Device self time of the Gauss-Newton-Bartlett curvature estimate
(scope `fed.gnb`: its sampled-label forward and backward and the
conditional around it), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "gnb")
