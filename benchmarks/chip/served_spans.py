"""The program's own served-path spans (``repro.obs.served``), as the
per-layer metrics that read them see them.

The program copies each span it opens while the profiler runs into an
in-memory buffer stamped on the host clock the profiler uses
(``time.time_ns``); the reduced trace counts from the session's start.
``aligned`` shifts the spans onto the trace's clock by the harness's
``classify`` span, which each request's ``coach.decide`` holds: the
classifier starts just after the decision opens and ends just before its
``coach.step``, so the midpoints of the two intervals agree to a few
microseconds.

A program without the module, an empty buffer or one that dropped spans
gives None, and so does every reader.
"""

from __future__ import annotations

from statistics import median

DECIDE, STEP, GC = "coach.decide", "coach.step", "coach.gc"


class Spans:
    """The spans of the window's requests: ``spans`` as recorded (parents
    index into it), ``mine`` the indices whose request is in the window,
    ``decides`` the index of each request's ``coach.decide`` in order."""

    def __init__(self, spans, ids):
        self.spans = spans
        self.mine = [i for i, s in enumerate(spans) if s.req in ids]
        self.decides = [i for i in self.mine if spans[i].name == DECIDE]
        self.n = len(self.decides)

    def self_ns(self, names) -> float:
        """Time in spans named ``names`` less their direct children's."""
        kids = {}
        for s in self.spans:
            if s.parent is not None:
                kids[s.parent] = kids.get(s.parent, 0) + s.t1 - s.t0
        return sum(self.spans[i].t1 - self.spans[i].t0 - kids.get(i, 0)
                   for i in self.mine if self.spans[i].name in names)

    def total_ns(self, name: str) -> float:
        return sum(self.spans[i].t1 - self.spans[i].t0 for i in self.mine
                   if self.spans[i].name == name)

    def count(self, name: str) -> int:
        return sum(self.spans[i].name == name for i in self.mine)


def load(ctx):
    """The window's spans, or None."""
    try:
        from repro.obs import served
    except ImportError:
        return None
    rec = served.recorded()
    if not rec.spans or rec.dropped \
            or any(s.t1 is None for s in rec.spans):
        return None
    got = Spans(rec.spans, {r["id"] for r in ctx.requests})
    return got if got.n else None


def offset(got: Spans, classify) -> float | None:
    """Trace time less span time, in ns, from the requests' ``classify``
    spans (one per request, in order)."""
    if len(classify) != got.n:
        return None
    steps = {}
    for i in got.mine:
        s = got.spans[i]
        if s.name == STEP:
            steps[s.parent] = s.t0
    shifts = [(c0 + c1 - got.spans[d].t0 - steps[d]) / 2
              for d, (c0, c1) in zip(got.decides, sorted(classify))
              if d in steps]
    return median(shifts) if shifts else None
