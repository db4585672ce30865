"""Device self time of the client rows (scope `fed.rows`: the gather
of the participants' rows from the resident stacks and the scatter of
their new rows back), in ms per round."""
from bench import phases


def read(ctx):
    return phases.phase_ms(ctx, "rows")
