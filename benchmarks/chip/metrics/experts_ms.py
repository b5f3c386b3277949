"""Expert kernel: device time per request of the grouped-matmul calls
(the ops named ``coach_grouped_matmul``, from
``kernels/grouped_matmul.py``) inside the two segment programs, in ms.
None where the segments hold no such op, as in a program whose expert
layers run the capacity dispatch."""

import kernel_time as KT

KERNEL = "coach_grouped_matmul"


def read(ctx):
    n = len(ctx.trace.spans.get("classify", []))
    took = KT.in_segments(ctx.trace, KERNEL)
    if not n or not took:
        return None
    return took / n * 1e-6
