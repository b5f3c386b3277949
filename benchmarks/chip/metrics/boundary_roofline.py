"""Fused boundary kernel (``kernels/boundary.py`` through
``ops.boundary_pass``, traced as the program ``jit_f``): the least time
its calls could take on this chip (bytes over HBM bandwidth, or FLOPs
over peak, whichever is larger, from shapes by ``flops.boundary_cost``)
over their device time, in %.  The i-th call of the window belongs to its
i-th request, whose count of trained centers sets the probe's shape."""

PROGRAM = "f"


def read(ctx):
    calls = ctx.trace.module_calls(PROGRAM)
    reqs = ctx.requests
    if not calls or len(calls) != len(reqs):
        return None
    s = ctx.shapes
    need = sum(ctx.flops.least_time(*ctx.flops.boundary_cost(
        s["seq_len"], ctx.cfg["hidden_size"],
        len(ctx.served[r["id"]]["valid"]), s["wire_bits"]), ctx.peak)
        for r in reqs)
    took = sum(e - b for b, e in calls) * 1e-9
    return need / took * 100.0
