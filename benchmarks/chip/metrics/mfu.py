"""Device, whole request: model FLOPs of every request served in the
window (``flops.request_flops``: routed experts only, causal attention
over the keys each query sees) over the sum of their service times at the
chip's peak bf16 FLOP/s, in %.  Service runs from the start of a
request's decision to its result on the host."""


def read(ctx):
    if not ctx.requests:
        return None
    busy = sum(r["end"] - r["start"] for r in ctx.requests)
    work = len(ctx.requests) * ctx.flops.request_flops(
        ctx.cfg, ctx.shapes["seq_len"])
    return work / (busy * ctx.peak["bf16_flops"]) * 100.0
