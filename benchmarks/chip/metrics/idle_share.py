"""Device: the share of the traced window in which no operation ran on the
chip, in %: 1 - (union of the busy intervals) / (window)."""


def read(ctx):
    w0, w1 = ctx.trace.window
    if w1 <= w0:
        return None
    return (1.0 - ctx.trace.busy_in(w0, w1) / (w1 - w0)) * 100.0
