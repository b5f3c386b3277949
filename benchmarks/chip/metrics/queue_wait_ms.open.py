"""Admission: 95th percentile of the wait from a request's due time to the
start of its service, in ms, on the harness clock.  Open loop only: one
request is served at a time, so a request that comes while another is
served waits."""

import numpy as np


def read(ctx):
    if ctx.loop != "open" or not ctx.requests:
        return None
    waits = [(r["start"] - r["due"]) * 1e3 for r in ctx.requests]
    return float(np.percentile(waits, 95))
