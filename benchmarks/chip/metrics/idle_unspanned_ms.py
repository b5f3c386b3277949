"""Glue outside the program: mean device idle per request, from the start
of its ``coach.decide`` to the end of its last span, that no other span
of the program covers, in ms.  That is the host in the harness's own
code (prompt upload, slices and pulls of the feature and the logits, the
argmax) or in program code no span names, while the chip waits."""

import bisect

import served_spans as SS
import tracing as TR


def read(ctx):
    got = SS.load(ctx)
    if got is None:
        return None
    off = SS.offset(got, ctx.trace.spans.get("classify", []))
    if off is None:
        return None
    by_req = {}
    for i in got.mine:
        by_req.setdefault(got.spans[i].req, []).append(got.spans[i])
    busy = ctx.trace.busy
    idle = 0.0
    for d in got.decides:
        spans = by_req[got.spans[d].req]
        lo = got.spans[d].t0 + off
        hi = max(s.t1 for s in spans) + off
        k = max(bisect.bisect_right(busy, (lo, float("inf"))) - 1, 0)
        near = []
        while k < len(busy) and busy[k][0] < hi:
            near.append(busy[k])
            k += 1
        near += [(s.t0 + off, s.t1 + off) for s in spans
                 if s.name != SS.DECIDE]
        idle += hi - lo - TR.covered(TR.union(near), lo, hi)
    return idle / got.n * 1e-6
