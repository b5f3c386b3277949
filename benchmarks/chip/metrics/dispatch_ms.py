"""Runtime host side: mean self time per request of the program's
``coach.segment``, ``coach.boundary`` and ``coach.dequantize`` spans,
the host's dispatch of the two segments, the fused boundary pass and the
wire dequantize, in ms."""

import served_spans as SS

NAMES = ("coach.segment", "coach.boundary", "coach.dequantize")


def read(ctx):
    got = SS.load(ctx)
    return None if got is None else got.self_ns(NAMES) / got.n * 1e-6
