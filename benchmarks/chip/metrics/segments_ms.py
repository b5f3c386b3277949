"""Segments: device time per request of the end and cloud segment
programs (``CollabRuntime._first_forward`` / ``_last_forward``, traced as
``jit__first_forward`` / ``jit__last_forward``), in ms."""

SEGMENTS = ("_first_forward", "_last_forward")


def read(ctx):
    n = len(ctx.trace.spans.get("classify", []))
    calls = [c for f in SEGMENTS for c in ctx.trace.module_calls(f)]
    if not n or not calls:
        return None
    return sum(e - s for s, e in calls) / n * 1e-6
