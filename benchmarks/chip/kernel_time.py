"""Device time of one kernel inside the two segment programs, for the
readers of a kernel's per-layer metrics."""

import bisect

import tracing as TR

SEGMENTS = ("_first_forward", "_last_forward")


def in_segments(trace, kernel: str) -> float:
    """Total device time, in ns, of the ops whose name starts with
    ``kernel`` inside ``jit__first_forward`` / ``jit__last_forward``."""
    mods = trace.modules
    starts = [s for _, s, _ in mods]
    took = 0.0
    for name, s, e in trace.ops:
        if not TR.op_name(name).startswith(kernel):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][0] in SEGMENTS and s < mods[i][2]:
            took += e - s
    return took
