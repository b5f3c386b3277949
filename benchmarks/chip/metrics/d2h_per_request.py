"""Runtime host side: device-to-host pulls per request in program code,
the count of its ``coach.d2h`` spans over the requests.  The harness's
own pulls (the feature and the logits) are not counted."""

import served_spans as SS


def read(ctx):
    got = SS.load(ctx)
    return None if got is None else got.count("coach.d2h") / got.n
