"""Pallas TPU kernel: fused UAQ quantize (+int4 pack) / dequantize.

This is the transmission hot-spot of COACH: every boundary activation is
quantized on the end pod before the cross-pod transfer and dequantized on
the cloud pod.  Fusing min/max -> scale -> round/clip -> nibble-pack into
one VMEM pass avoids three HBM round-trips of the fp32 tensor.

TPU adaptation (vs the paper's GPU/CPU quantizer):
  - rows are tiled in blocks of ``block_m``; the full channel dim N stays
    resident in VMEM (lane-aligned, N % 128 == 0 for production shapes);
  - reductions run on the VPU over the 128-lane axis;
  - int4 values are packed two-per-byte with shift/or on int32 then cast,
    halving ICI/DCN bytes (the roofline's collective term); byte ``i``
    pairs channel ``i`` with channel ``i + ceil(N/2)`` (``pack4``), so
    packing and unpacking use contiguous lane slices only;
  - any row count M: rows are zero-padded up to a block multiple and the
    pad rows sliced off.

Validated against ``ref.uaq_*`` in interpret mode (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pack4(q: jnp.ndarray) -> jnp.ndarray:
    """The int4 wire format: uint4 values (..., N) -> (..., H) bytes,
    H = ceil(N/2), byte ``i`` holding channel ``i`` in its low nibble and
    channel ``i + H`` in its high nibble.  Two contiguous half slices are
    what Mosaic lowers (a stride-2 channel slice is not).  An odd N pads
    one zero nibble in the *quantized* domain, so the row's scale and
    zero-point, computed on the true N values, are untouched."""
    h = (q.shape[-1] + 1) // 2
    lo, hi = q[..., :h], q[..., h:]
    if hi.shape[-1] < h:
        hi = jnp.concatenate([hi, jnp.zeros_like(lo[..., :1])], axis=-1)
    return (lo | (hi << 4)).astype(jnp.uint8)


def _quant_kernel(x_ref, out_ref, scale_ref, zp_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)  # (bm, N)
    qmax = float((1 << bits) - 1)
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-8) / qmax
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(x / scale + zp), 0.0, qmax).astype(jnp.int32)
    # int4: the consumer slices an odd N's pad nibble back off with N
    out_ref[...] = pack4(q) if bits == 4 else q.astype(jnp.uint8)
    scale_ref[...] = scale
    zp_ref[...] = zp


def _dequant_kernel(p_ref, scale_ref, zp_ref, out_ref, *, bits: int,
                    out_dtype, n: int):
    p = p_ref[...].astype(jnp.int32)
    scale, zp = scale_ref[...], zp_ref[...]

    def dq(q):
        return ((q.astype(jnp.float32) - zp) * scale).astype(out_dtype)

    if bits == 4:
        # low nibbles are channels [0, H), high nibbles [H, n): two
        # lane-contiguous stores, no interleave
        h = p.shape[1]
        out_ref[:, :h] = dq(p & 0xF)
        out_ref[:, h:] = dq(p >> 4)[:, :n - h]
    else:
        out_ref[...] = dq(p)


def _row_block(M: int, block_m: int):
    """Rows per grid step and the zero-row pad that makes them divide M
    (pad rows quantize to a constant row and are sliced off)."""
    bm = min(block_m, M)
    return bm, -M % bm


def uaq_quantize(x: jnp.ndarray, bits: int, block_m: int = 256,
                 interpret: bool | None = None):
    """x: (M, N) -> (packed (M, ceil(N*bits/8)) uint8, scale (M,1),
    zp (M,1)).  An odd N at 4 bits is zero-nibble padded in the packed
    payload; pass ``n=N`` to ``uaq_dequantize`` to slice back exactly."""
    assert bits in (4, 8), "wire format supports int4 (packed) and int8"
    M, N = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bm, pad = _row_block(M, block_m)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    Mp = M + pad
    n_out = (N + 1) // 2 if bits == 4 else N
    packed, scale, zp = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, n_out), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, n_out), jnp.uint8),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return packed[:M], scale[:M], zp[:M]


def uaq_dequantize(packed: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray,
                   bits: int, out_dtype=jnp.float32, block_m: int = 256,
                   interpret: bool | None = None, n: int | None = None):
    """``n`` is the true channel count when the 4-bit payload carries an
    odd-N zero-nibble pad (defaults to the payload's full width)."""
    assert bits in (4, 8)
    M, n_in = packed.shape
    N = n if n is not None else n_in * 8 // bits
    assert N <= n_in * 8 // bits
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bm, pad = _row_block(M, block_m)
    if pad:
        packed, scale, zp = (jnp.pad(a, ((0, pad), (0, 0)))
                             for a in (packed, scale, zp))
    Mp = M + pad
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits, out_dtype=out_dtype,
                          n=N),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, n_in), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        interpret=interpret,
    )(packed, scale, zp)
    return out[:M]
