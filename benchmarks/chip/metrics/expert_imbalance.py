"""Expert routing: the mean, over the window's requests and their expert
layers, of the busiest expert's tokens over the mean expert's, from the
program's ``coach.experts`` spans (meta ``loads``).  1 is a perfect
balance; the layer's time follows its busiest expert.  None where the
program records no such span."""

import served_spans as SS

EXPERTS = "coach.experts"


def read(ctx):
    got = SS.load(ctx)
    if got is None:
        return None
    ratios = [max(loads) * len(loads) / sum(loads)
              for loads in (got.spans[i].meta["loads"] for i in got.mine
                            if got.spans[i].name == EXPERTS)
              if sum(loads)]
    return sum(ratios) / len(ratios) if ratios else None
