"""Device self time of the Gauss-Newton-Bartlett curvature estimate
(scope `fed.gnb`: its sampled-label forward and backward and the
conditional around it), in ms per round."""


def read(ctx):
    return ctx.phase_ms("gnb")
