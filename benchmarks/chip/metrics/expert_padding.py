"""Expert kernel: the rows it computes over the rows routed to it, the
mean over the window's requests and their expert layers, from the
program's ``coach.experts`` spans (meta ``rows`` and ``loads``).  1 is no
padding; each expert's rows are padded to whole row blocks.  None where
the program records no such span, or no ``rows`` in it."""

import served_spans as SS

EXPERTS = "coach.experts"


def read(ctx):
    got = SS.load(ctx)
    if got is None:
        return None
    ratios = [s.meta["rows"] / sum(s.meta["loads"])
              for s in (got.spans[i] for i in got.mine)
              if s.name == EXPERTS and "rows" in s.meta
              and sum(s.meta["loads"])]
    return sum(ratios) / len(ratios) if ratios else None
