"""Runtime host side: mean device idle per request under the program's
device-to-host pulls (its ``coach.d2h`` spans), in ms.  A pull that waits
while the chip still computes costs nothing here; one made after the chip
has finished counts its whole wait.  Spans that overlap count once."""

import served_spans as SS
import tracing as TR


def read(ctx):
    got = SS.load(ctx)
    if got is None:
        return None
    off = SS.offset(got, ctx.trace.spans.get("classify", []))
    if off is None:
        return None
    pulls = TR.union((s.t0 + off, s.t1 + off) for s in
                     (got.spans[i] for i in got.mine)
                     if s.name == "coach.d2h")
    busy = ctx.trace.busy
    idle = sum(hi - lo - TR.covered(busy, lo, hi) for lo, hi in pulls)
    return idle / got.n * 1e-6
