"""Expert kernel: the least time of a request's routed expert work on this
chip (``expert_cost.expert_cost`` per expert layer: FLOPs at peak or
bytes at HBM bandwidth, whichever is larger) over the device time per
request of its ``coach_grouped_matmul`` calls in the segments, in %.
None where there are none."""

import expert_cost as EC
import kernel_time as KT

KERNEL = "coach_grouped_matmul"


def read(ctx):
    n = len(ctx.trace.spans.get("classify", []))
    took = KT.in_segments(ctx.trace, KERNEL) * 1e-9
    if not n or not took:
        return None
    cfg = ctx.cfg
    need = cfg["num_hidden_layers"] * ctx.flops.least_time(
        *EC.expert_cost(cfg, ctx.shapes["seq_len"]), ctx.peak)
    return need / (took / n) * 100.0
