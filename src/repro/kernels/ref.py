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


# ------------------------------------------------------------ attention ref
def causal_attention_ref(q, k, v, scale: float, window=None):
    """Causal grouped-query attention, the math of ``layers._attend``:
    q (B, S, H, hd), k / v (B, S, KV, hd) -> (B, S, H, hd).  Scores in the
    inputs' dtype, then f32 times ``scale``; keys ``q - window < k <= q``
    visible (``None``: every earlier key); f32 softmax cast to the value
    dtype before PV."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qr = q.reshape(B, S, KV, H // KV, hd)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qr, k).astype(jnp.float32) \
        * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    vis = pos[None, :] <= pos[:, None]
    if window is not None:
        vis &= pos[None, :] > pos[:, None] - window
    logits = jnp.where(vis[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w, v)
    return out.reshape(B, S, H, hd)


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


# ---------------------------------------------------- grouped matmul ref
def grouped_matmul_ref(lhs, rhs, group_sizes, act=None):
    """``grouped_matmul``'s math per group: lhs (M, K) rows sorted by
    group, rhs a tuple of one or two (G, K, N) -> (M, N); row ``i`` of
    group ``g`` is ``lhs[i] @ rhs[0][g]``, or with two matrices
    ``act(lhs[i] @ rhs[0][g]) * (lhs[i] @ rhs[1][g])``, products in f32,
    cast to ``lhs``'s dtype.  Rows after the last group's are zero."""
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = 0.0
    for g in range(rhs[0].shape[0]):
        y = [jnp.dot(lhs, w[g], preferred_element_type=jnp.float32)
             for w in rhs]
        y = act(y[0]) * y[1] if len(y) == 2 else y[0]
        out = out + jnp.where((gid == g)[:, None], y, 0.0)
    return out.astype(lhs.dtype)


# -------------------------------------------- dropless mixture of experts
def moe_dropless_ref(x, router, w_gate, w_up, w_down, k: int,
                     act=jax.nn.silu):
    """The published top-k mixture of experts, routing every token:
    x (T, D), router (D, E), w_gate / w_up (E, D, F), w_down (E, F, D)
    -> (T, D) float32.  A float32 softmax router, the top-k gates
    renormalized to sum to one; each expert's gated FFN applied to every
    token, weighted by that token's gate for the expert (zero where the
    expert is not among its top k).  Everything in float32 at the
    highest matmul precision: the plain reference, one expert at a time."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(x)
        probs = jax.nn.softmax(x @ f32(router), axis=-1)
        top, ids = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(router.shape[1]):
            gate = jnp.sum(jnp.where(ids == e, top, 0.0), axis=-1)
            h = act(x @ f32(w_gate[e])) * (x @ f32(w_up[e]))
            y = y + gate[:, None] * (h @ f32(w_down[e]))
    return y
