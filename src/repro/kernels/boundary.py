"""Pallas TPU kernel: single-pass fused boundary hop
(quantize -> int4 pack -> semantic probe).

COACH's per-boundary hot path executes three ops on the same (B, S, D)
activation: UAQ row-quantize it for the wire (Eq. 1), pack the nibbles,
and probe the GAP feature against the semantic-cache centers (Eq. 8-9).
Run separately, the fp32 tensor crosses HBM once per op.  This kernel
fuses all of them so the activation is read exactly once per hop:

  grid (B blocks, S blocks); per step the (bb, bs, D) tile is
    1. row-quantized (per-token min/max -> scale/zp -> round/clip) and
       nibble-packed straight into the payload/scale/zp output blocks,
    2. summed over its sequence slice into a VMEM scratch accumulator
       (the ``semantic_cache.py`` idiom);
  the epilogue on the last S step finishes GAP -> L2-normalize ->
  cosine-vs-centers (MXU) -> top-2 -> separability and writes
  feat/sep/best/sims.

The GAP feature comes out alongside the wire packet, so the online
component (Eq. 7 center updates) needs no second read either.

Validated bit-for-bit against ``ref.fused_boundary_ref`` and against the
unfused (``uaq_quantize`` o ``semantic_probe``) composition in interpret
mode (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.semantic_cache import HIGHEST, VMEM_LIMIT, seq_block
from repro.kernels.uaq import pack4


def _boundary_kernel(x_ref, c_ref, payload_ref, scale_ref, zp_ref,
                     feat_ref, sep_ref, best_ref, sims_ref, acc_ref, *,
                     bits: int, n_s_blocks: int, seq_len: int):
    sj = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (bb, bs, D)

    # ---- per-token UAQ quantize + pack (writes this tile's wire blocks)
    qmax = float((1 << bits) - 1)
    lo = jnp.min(x, axis=2, keepdims=True)
    hi = jnp.max(x, axis=2, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(x / scale + zp), 0.0, qmax).astype(jnp.int32)
    # int4: the wire's half-split nibble pack (``uaq.pack4``)
    payload_ref[...] = pack4(q) if bits == 4 else q.astype(jnp.uint8)
    scale_ref[...] = scale
    zp_ref[...] = zp

    # ---- GAP accumulation over the sequence axis (VMEM scratch)
    @pl.when(sj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(x, axis=1)

    # ---- probe epilogue on the last S step (Eq. 8-9)
    @pl.when(sj == n_s_blocks - 1)
    def _epilogue():
        f = acc_ref[...] / seq_len  # GAP   (bb, D); true S, pad-exact
        fn = f / jnp.maximum(
            jnp.sqrt(jnp.sum(f * f, axis=1, keepdims=True)), 1e-12)
        c = c_ref[...].astype(jnp.float32)  # (L, D)
        cn = c / jnp.maximum(
            jnp.sqrt(jnp.sum(c * c, axis=1, keepdims=True)), 1e-12)
        sims = (jnp.dot(fn, cn.T, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
                + 1.0) * 0.5  # Eq. 8 -> [0,1]
        L = sims.shape[1]
        t_h = jnp.max(sims, axis=1)
        best = jnp.argmax(sims, axis=1).astype(jnp.int32)
        onehot = best[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
        t_sh = jnp.max(jnp.where(onehot, -jnp.inf, sims), axis=1)
        norm = jnp.sqrt(jnp.sum(sims * sims, axis=1))
        sep = norm * (t_h - t_sh) * t_h / jnp.maximum(t_sh, 1e-12)  # Eq. 9
        feat_ref[...] = f
        sep_ref[...] = sep[:, None]
        best_ref[...] = best[:, None]
        sims_ref[...] = sims


def fused_boundary(x: jnp.ndarray, centers: jnp.ndarray, bits: int,
                   block_b: int = 8, block_s: int = 512,
                   interpret: bool | None = None):
    """x: (B,S,D), centers: (L,D) -> (payload (B,S,P) uint8,
    scale (B,S,1), zp (B,S,1), feat (B,D), sep (B,), best (B,),
    sims (B,L)); P = ceil(D * bits / 8).

    ``B``/``S`` need not divide the block sizes (zero-padded up to block
    multiples, pad rows sliced off; the GAP epilogue divides by the true
    ``S``, so padding is exact — see ``semantic_cache.semantic_probe``).
    An odd ``D`` at 4 bits is zero-nibble padded in the payload; the
    consumer slices back with the true channel count."""
    assert bits in (4, 8), "wire format supports int4 (packed) and int8"
    B, S, D = x.shape
    L = centers.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bb = min(block_b, B)
    bs = seq_block(S, bb, D, block_s)
    pad_b = -B % bb
    pad_s = -S % bs
    if pad_b or pad_s:
        x = jnp.pad(x, ((0, pad_b), (0, pad_s), (0, 0)))
    Bp, Sp = B + pad_b, S + pad_s
    P = (D + 1) // 2 if bits == 4 else D
    grid = (Bp // bb, Sp // bs)
    payload, scale, zp, feat, sep, best, sims = pl.pallas_call(
        functools.partial(_boundary_kernel, bits=bits,
                          n_s_blocks=Sp // bs, seq_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bs, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((L, D), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bs, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, bs, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, bs, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, L), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Sp, P), jnp.uint8),
            jax.ShapeDtypeStruct((Bp, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, D), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, L), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, centers)
    return (payload[:B, :S], scale[:B, :S], zp[:B, :S], feat[:B],
            sep[:B, 0], best[:B, 0], sims[:B])
