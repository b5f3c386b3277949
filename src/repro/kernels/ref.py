"""Pure-jnp oracles for the Pallas kernels (the correctness contract).

Every kernel in this package is validated against these references across
shape/dtype/bit sweeps in tests/test_kernels.py (interpret mode on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the probe's cosine matmul runs at full f32 accuracy on every backend
# (the TPU's default f32 matmul rounds its inputs to bf16), so kernel and
# reference agree on a chip as they do in interpret mode
HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- UAQ ref
def uaq_rowwise_ref(x: jnp.ndarray, bits: int):
    """Row-wise UAQ: x (M, N) -> (q (M,N) uint8, scale (M,1), zp (M,1)).

    q in [0, 2^bits - 1]; scale/zp per row (the boundary-tensor layout used
    by the collaborative executor: rows = tokens, cols = channels)."""
    qmax = (1 << bits) - 1
    xf = x.astype(jnp.float32)  # contract: all quant math in f32
    lo = jnp.min(xf, axis=1, keepdims=True)
    hi = jnp.max(xf, axis=1, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(xf / scale + zp), 0, qmax)
    return q.astype(jnp.uint8), scale, zp


def pack4_ref(q: jnp.ndarray) -> jnp.ndarray:
    """Pack uint4 values (..., N) -> (..., H) bytes, H = ceil(N/2): byte
    ``i`` holds channel ``i`` in its low nibble and channel ``i + H`` in
    its high nibble (the layout of ``uaq.pack4``, written independently
    here: zero-pad to 2H, then split the channel axis into its halves).
    An odd N's pad is a zero nibble in the *quantized* domain, so the
    row's scale/zero-point are untouched and ``unpack4_ref(..., n=N)``
    recovers the row exactly."""
    n = q.shape[-1]
    h = (n + 1) // 2
    q2 = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 2 * h - n)])
    halves = q2.reshape(*q.shape[:-1], 2, h).astype(jnp.int32)
    return (halves[..., 0, :] + halves[..., 1, :] * 16).astype(jnp.uint8)


def unpack4_ref(p: jnp.ndarray, n: int | None = None) -> jnp.ndarray:
    """Unpack nibbles (..., H) -> (..., 2H), sliced to the true channel
    count ``n`` when the producer zero-padded an odd N."""
    q = jnp.concatenate([p & 0xF, p >> 4], axis=-1)
    return q if n is None else q[..., :n]


def uaq_quantize_ref(x, bits: int):
    q, scale, zp = uaq_rowwise_ref(x, bits)
    if bits == 4:
        return pack4_ref(q), scale, zp
    return q, scale, zp


def uaq_dequantize_ref(packed, scale, zp, bits: int, out_dtype=jnp.float32,
                       n: int | None = None):
    q = unpack4_ref(packed, n=n) if bits == 4 else packed
    return ((q.astype(jnp.float32) - zp) * scale).astype(out_dtype)


# ------------------------------------------------------ fused boundary ref
def fused_boundary_ref(x: jnp.ndarray, centers: jnp.ndarray, bits: int):
    """Exact jnp mirror of ``boundary.fused_boundary`` (the single-pass
    quantize -> pack -> probe kernel): same expression sequence, so the
    kernel is pinned bit-for-bit in interpret mode.

    x: (B, S, D) boundary activation; centers: (L, D).  Returns
    (payload (B,S,P) uint8, scale (B,S,1), zp (B,S,1), feat (B,D),
    sep (B,), best (B,) int32, sims (B,L)) — the per-token wire packet
    fields plus the per-task GAP feature and probe outputs, from one
    logical read of ``x``."""
    B, S, D = x.shape
    qmax = float((1 << bits) - 1)
    xf = x.astype(jnp.float32)
    lo = jnp.min(xf, axis=2, keepdims=True)
    hi = jnp.max(xf, axis=2, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(xf / scale + zp), 0.0, qmax).astype(jnp.int32)
    if bits == 4:
        payload = pack4_ref(q)
    else:
        payload = q.astype(jnp.uint8)
    f = jnp.sum(xf, axis=1) / S  # GAP (sum-then-divide, like the kernel)
    fn = f / jnp.maximum(
        jnp.sqrt(jnp.sum(f * f, axis=1, keepdims=True)), 1e-12)
    c = centers.astype(jnp.float32)
    cn = c / jnp.maximum(
        jnp.sqrt(jnp.sum(c * c, axis=1, keepdims=True)), 1e-12)
    sims = (jnp.dot(fn, cn.T, precision=HIGHEST,
                    preferred_element_type=jnp.float32)
            + 1.0) * 0.5  # Eq. 8 -> [0,1]
    L = sims.shape[1]
    t_h = jnp.max(sims, axis=1)
    best = jnp.argmax(sims, axis=1).astype(jnp.int32)
    onehot = best[:, None] == jnp.arange(L, dtype=jnp.int32)[None, :]
    t_sh = jnp.max(jnp.where(onehot, -jnp.inf, sims), axis=1)
    norm = jnp.sqrt(jnp.sum(sims * sims, axis=1))
    sep = norm * (t_h - t_sh) * t_h / jnp.maximum(t_sh, 1e-12)  # Eq. 9
    return payload, scale, zp, f, sep, best, sims


# ------------------------------------------------------- semantic cache ref
def semantic_probe_ref(x: jnp.ndarray, centers: jnp.ndarray):
    """Fused GAP + cosine similarity + top-2 separability (Eq. 8-10).

    x: (B, S, D) intermediate activations; centers: (L, D) label semantic
    centers.  Returns (sep (B,), best (B,) int32, sims (B, L) in [0,1]).
    """
    f = jnp.mean(x.astype(jnp.float32), axis=1)  # GAP over sequence
    fn = f / jnp.maximum(jnp.linalg.norm(f, axis=1, keepdims=True), 1e-12)
    cn = centers.astype(jnp.float32)
    cn = cn / jnp.maximum(jnp.linalg.norm(cn, axis=1, keepdims=True), 1e-12)
    sims = (jnp.dot(fn, cn.T, precision=HIGHEST)
            + 1.0) * 0.5  # Eq. 8, mapped to [0,1]
    t_h = jnp.max(sims, axis=1)
    best = jnp.argmax(sims, axis=1).astype(jnp.int32)
    masked = jnp.where(
        jax_one_hot(best, sims.shape[1], dtype=bool), -jnp.inf, sims)
    t_sh = jnp.max(masked, axis=1)
    norm = jnp.linalg.norm(sims, axis=1)
    sep = norm * (t_h - t_sh) * t_h / jnp.maximum(t_sh, 1e-12)  # Eq. 9
    return sep, best, sims


def jax_one_hot(idx, n, dtype=bool):
    return (idx[:, None] == jnp.arange(n)[None, :]).astype(dtype)
