"""Wire dequantize (``kernels/uaq.py`` through
``ops.dequantize_activation``, traced as ``jit__dequantize``): the least
time of its calls (``flops.dequant_cost``, bytes bound) over their device
time, in %."""

PROGRAM = "_dequantize"


def read(ctx):
    calls = ctx.trace.module_calls(PROGRAM)
    if not calls:
        return None
    s = ctx.shapes
    need = len(calls) * ctx.flops.least_time(*ctx.flops.dequant_cost(
        s["seq_len"], ctx.cfg["hidden_size"], s["wire_bits"]), ctx.peak)
    took = sum(e - b for b, e in calls) * 1e-9
    return need / took * 100.0
