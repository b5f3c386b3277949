"""Pallas TPU kernel: fused semantic-cache probe (COACH online hot-spot).

Fuses GAP over the sequence axis -> L2-normalize -> cosine similarity
against all label semantic centers (MXU matmul) -> top-2 -> task
separability (Eq. 9) in a single kernel, so the (B,S,D) activation is read
from HBM exactly once and the (B,L) similarity matrix never round-trips.

Grid: (B blocks, S blocks).  The S axis is accumulated into a VMEM scratch
(f32) across grid steps; the similarity/top-2 epilogue runs on the last S
step.  Centers stay fully resident in VMEM (L x D; L=#labels is small).

Validated against ``ref.semantic_probe_ref`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the probe's cosine matmul runs at full f32 accuracy (the TPU's default
# f32 matmul rounds its inputs to bf16), as the jnp reference's does
HIGHEST = jax.lax.Precision.HIGHEST

# f32 bytes of one (bb, bs, D) working tile: the sequence block shrinks
# as D grows so the double-buffered input tile and the kernel body's f32
# temporaries stay inside VMEM_LIMIT at production widths (D = 3840 gives
# bs = 32 at bb = 8), while test-sized D keeps a single sequence block
TILE_BYTES = 4 << 20
VMEM_LIMIT = 32 << 20  # scoped VMEM the (B, S) tiled kernels may use


def seq_block(S: int, bb: int, D: int, block_s: int) -> int:
    """Sequence rows per grid step: at most ``block_s`` and the largest
    multiple of 8 whose f32 (bb, bs, D) tile fits ``TILE_BYTES`` (or the
    whole sequence, when that is shorter)."""
    fit = max(8, TILE_BYTES // (4 * bb * D) // 8 * 8)
    return min(S, block_s, fit)


def _probe_kernel(x_ref, c_ref, sep_ref, best_ref, sims_ref, acc_ref, *,
                  n_s_blocks: int, seq_len: int):
    sj = pl.program_id(1)

    @pl.when(sj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(x_ref[...].astype(jnp.float32), axis=1)

    @pl.when(sj == n_s_blocks - 1)
    def _epilogue():
        f = acc_ref[...] / seq_len  # GAP   (bb, D)
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
        sep_ref[...] = sep[:, None]
        best_ref[...] = best[:, None]
        sims_ref[...] = sims


def semantic_probe(x: jnp.ndarray, centers: jnp.ndarray,
                   block_b: int = 8, block_s: int = 512,
                   interpret: bool | None = None):
    """x: (B,S,D), centers: (L,D) -> (sep (B,), best (B,), sims (B,L)).

    ``B``/``S`` need not divide the block sizes: the batch and sequence
    axes are zero-padded up to block multiples and the pad rows sliced
    off.  The GAP epilogue divides the VMEM accumulator by the *true*
    ``S``, so sequence padding contributes exactly zero to the mean and
    the padded result is bit-identical to the unpadded one."""
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
    grid = (Bp // bb, Sp // bs)
    sep, best, sims = pl.pallas_call(
        functools.partial(_probe_kernel, n_s_blocks=Sp // bs, seq_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bs, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((L, D), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, L), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, L), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, centers)
    return sep[:B, 0], best[:B, 0], sims[:B]
