"""Host runtime: the collector's pauses in the window (the program's
``coach.gc`` spans) per request, in ms.  A pause belongs to the request
whose decision came last before it."""

import served_spans as SS


def read(ctx):
    got = SS.load(ctx)
    return None if got is None else got.total_ns("coach.gc") / got.n * 1e-6
