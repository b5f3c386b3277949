"""Engine host, inside the program: mean self time per request of its
``coach.step``, ``coach.plan``, ``coach.account`` and
``coach.probe_centers`` spans (each less the spans it holds, such as a
collector pause), in ms.  It refines ``sched_ms``, the same work timed
from outside the program."""

import served_spans as SS

NAMES = ("coach.step", "coach.plan", "coach.account", "coach.probe_centers")


def read(ctx):
    got = SS.load(ctx)
    return None if got is None else got.self_ns(NAMES) / got.n * 1e-6
