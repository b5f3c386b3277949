"""Operations and bytes of a request and of each kernel call, from shapes.

Model FLOPs (``request_flops``) count what the model needs, not what the
program executes: multiply-adds as 2 operations, every projection, causal
attention over the keys each query sees (about half of the full score
and value products), routed expert
assignments only (``num_experts_per_tok`` per token), the router, and the
head at the one position the cloud segment returns.  Norms, softmax,
RoPE and the embedding gather are left out (under 0.1% at these sizes).

Kernel bytes count the HBM traffic the algorithm needs for one call:
each input read once and each output written once.  Kernel FLOPs count
the arithmetic on the matrix unit only (the probe's cosine product); the
elementwise work runs on the vector unit, so both wire kernels are bound
by bytes.
"""

from __future__ import annotations

import math


def attention_span(seq: int, window: int) -> float:
    """Mean number of keys a causal query attends to, with a local window
    of ``window`` positions (a query sees itself and the keys before it)."""
    w = min(window, seq)
    # queries 0..w-1 see i+1 keys, the rest see w
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def layer_flops(cfg: dict, seq: int) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    proj = 2 * seq * d * (h * hd + 2 * kv * hd) + 2 * seq * h * hd * d
    # causal: each query's score and value products over the keys it sees
    attn = 2 * 2 * seq * attention_span(seq, cfg["sliding_window"]) * h * hd
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    if e:
        ffn = 2 * seq * k * 3 * d * f + 2 * seq * d * e
    else:
        ffn = 2 * seq * 3 * d * f
    return float(proj + attn + ffn)


def request_flops(cfg: dict, seq: int) -> float:
    """Model FLOPs of one batch-1 request of ``seq`` tokens."""
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_flops(cfg, seq) + head


def boundary_cost(seq: int, d: int, n_centers: int, bits: int,
                  x_bytes: int = 2):
    """(FLOPs, bytes) of one fused boundary pass over a (1, seq, d)
    activation: read it and the centers, write the packed payload, the
    per-token scale and zero-point, the GAP feature and the probe
    outputs."""
    payload = seq * math.ceil(d * bits / 8)
    read = seq * d * x_bytes + n_centers * d * 4
    write = payload + 2 * seq * 4 + d * 4 + 4 + 4 + n_centers * 4
    return float(2 * n_centers * d), float(read + write)


def dequant_cost(seq: int, d: int, bits: int, out_bytes: int = 2):
    """(FLOPs, bytes) of one wire dequantize of a (seq, d) packet into the
    cloud segment's dtype."""
    payload = seq * math.ceil(d * bits / 8)
    return 0.0, float(payload + 2 * seq * 4 + seq * d * out_bytes)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's bound on one call: the larger of its operations at
    peak FLOP/s and its bytes at peak bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
