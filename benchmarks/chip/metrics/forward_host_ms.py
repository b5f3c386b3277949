"""Launcher glue and runtime host side: mean time per request inside the
classifier span (prompt upload, probe centers, segment and kernel
dispatch, device-to-host pulls, ``ProbeResult``) during which the device
ran nothing, in ms.  Every device operation of the window runs inside a
classifier span, so this is the spans' mean length less the window's
busy time per request: two durations, one on each clock, and no reliance
on how closely the trace aligns the clocks (within about 0.4 ms)."""


def read(ctx):
    spans = ctx.trace.spans.get("classify", [])
    if not spans:
        return None
    busy = ctx.trace.busy_in(*ctx.trace.window)
    return (sum(e - s for s, e in spans) - busy) / len(spans) * 1e-6
