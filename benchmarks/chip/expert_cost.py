"""Operations and bytes of one expert layer's routed work, from shapes.

The count is what the layer needs, however a kernel splits or fuses it:
each token's ``num_experts_per_tok`` assignments through its expert's
gated FFN (gate, up and down products, multiply-adds as 2 operations),
each expert's three weight matrices read once, the routed rows read once
and their outputs written once.  Padding rows to whole blocks, and
experts left idle, add time and nothing here, so they lower the
roofline share and never raise it.
"""

from __future__ import annotations

import jax.numpy as jnp


def expert_cost(cfg: dict, tokens: int):
    """(FLOPs, bytes) of one expert layer over ``tokens`` tokens."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    size = jnp.dtype(cfg["dtype"]).itemsize
    routed = tokens * k
    flops = 2 * routed * 3 * d * f
    nbytes = 3 * e * d * f * size + 2 * routed * d * size
    return float(flops), float(nbytes)
