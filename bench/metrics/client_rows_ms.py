"""Device self time of the client rows (scope `fed.rows`: the gather
of the participants' rows from the resident stacks and the scatter of
their new rows back), in ms per round."""


def read(ctx):
    return ctx.phase_ms("rows")
