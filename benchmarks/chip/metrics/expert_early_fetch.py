"""Expert kernel: the share of its weight-block copies started two or
more visits before their first use, in %, the mean over the window's
requests and their expert layers, from the program's ``coach.experts``
spans (meta ``early``).  None where the program records no such span, or
no ``early`` in it."""

import served_spans as SS

EXPERTS = "coach.experts"


def read(ctx):
    got = SS.load(ctx)
    if got is None:
        return None
    early = [s.meta["early"] for s in (got.spans[i] for i in got.mine)
             if s.name == EXPERTS and "early" in s.meta]
    return sum(early) / len(early) * 100 if early else None
