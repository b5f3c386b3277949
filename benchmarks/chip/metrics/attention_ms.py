"""Attention kernel: device time per request of the fused flash
attention calls (the ops named ``coach_flash_attention``, from
``kernels/attention.py``) inside the two segment programs
``jit__first_forward`` / ``jit__last_forward``, in ms.  None where the
segments hold no such op."""

import bisect

import tracing as TR

KERNEL = "coach_flash_attention"
SEGMENTS = ("_first_forward", "_last_forward")


def read(ctx):
    n = len(ctx.trace.spans.get("classify", []))
    mods = ctx.trace.modules
    starts = [s for _, s, _ in mods]
    took = 0.0
    for name, s, e in ctx.trace.ops:
        if not TR.op_name(name).startswith(KERNEL):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][0] in SEGMENTS and s < mods[i][2]:
            took += e - s
    if not n or not took:
        return None
    return took / n * 1e-6
