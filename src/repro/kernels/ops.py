"""Jit'd public wrappers around the Pallas kernels.

``quantize_activation`` / ``dequantize_activation`` handle arbitrary-rank
boundary tensors (flattened to (tokens, channels)), and fall back to the
pure-jnp reference for bit-widths outside the packed wire formats (the cost
model still prices those; only 4/8-bit have a TPU wire kernel).

``boundary_pass`` is the fused single-pass boundary hop (quantize + pack +
probe in one HBM read, ``kernels.boundary``); off-TPU it dispatches to the
exact jnp reference.

``wire_quantize`` / ``wire_dequantize`` are the *trace-safe* shared wire
entry points: plain functions (no jit wrapper) that pick the Pallas kernel
on TPU and the jnp reference elsewhere, so they can be traced inside
``shard_map`` regions where interpret-mode Pallas cannot compile (see
``core.collab.make_collab_pipeline_step``).
"""

from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.boundary import fused_boundary
from repro.kernels.uaq import uaq_dequantize, uaq_quantize
from repro.kernels.semantic_cache import semantic_probe

KERNEL_BITS = (4, 8)

# calls of the wire entry points by (op, bits, path), where path is what
# ran: "pallas" = a Pallas kernel compiled for the TPU, "interpret" = the
# same kernel in interpret mode (off TPU), "ref" = the jnp reference.  It
# is bumped in the Python wrappers, so it counts eager calls: a call traced
# inside an outer jit counts once per trace.  chip_smoke.py reads it to
# show which path the served hops took.
PATHS: collections.Counter = collections.Counter()


def _count(op: str, bits: int, kernel: bool) -> bool:
    path = ("ref" if not kernel else
            "pallas" if jax.default_backend() == "tpu" else "interpret")
    PATHS[op, int(bits), path] += 1
    return kernel


def _as2d(x):
    return x.reshape(-1, x.shape[-1]), x.shape


def quantize_activation(x, bits: int = 8, use_kernel: bool = True):
    """(..., N) -> (packed (..., ceil(N*bits/8)) uint8, scale, zp).  An
    odd N at 4 bits carries a zero-nibble pad; dequantize with
    ``channels=N`` to slice back exactly."""
    kernel = _count("quantize", bits, use_kernel and bits in KERNEL_BITS)
    return _quantize(x, bits=bits, use_kernel=kernel)


@functools.partial(jax.jit, static_argnames=("bits", "use_kernel"))
def _quantize(x, bits: int, use_kernel: bool):
    x2, shape = _as2d(x)
    if use_kernel:
        p, s, z = uaq_quantize(x2, bits)
    else:
        p, s, z = ref.uaq_quantize_ref(x2, bits)
    lead = shape[:-1]
    return (p.reshape(*lead, -1), s.reshape(*lead, 1), z.reshape(*lead, 1))


def dequantize_activation(packed, scale, zp, bits: int = 8,
                          out_dtype=jnp.float32, use_kernel: bool = True,
                          channels: Optional[int] = None):
    """``channels`` is the true channel count when the 4-bit payload was
    packed from an odd N (defaults to the payload's full width)."""
    kernel = _count("dequantize", bits, use_kernel and bits in KERNEL_BITS)
    return _dequantize(packed, scale, zp, bits=bits, out_dtype=out_dtype,
                       use_kernel=kernel, channels=channels)


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype",
                                             "use_kernel", "channels"))
def _dequantize(packed, scale, zp, bits: int, out_dtype, use_kernel: bool,
                channels: Optional[int]):
    p2, shape = _as2d(packed)
    s2 = scale.reshape(-1, 1)
    z2 = zp.reshape(-1, 1)
    if use_kernel:
        x = uaq_dequantize(p2, s2, z2, bits, out_dtype, n=channels)
    else:
        x = ref.uaq_dequantize_ref(p2, s2, z2, bits, out_dtype, n=channels)
    return x.reshape(*shape[:-1], -1)


@jax.jit
def probe_cache(x, centers) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused GAP+cosine+separability.  x: (B,S,D); centers: (L,D)."""
    return semantic_probe(x, centers)


# ------------------------------------------------- fused boundary pass
@functools.lru_cache(maxsize=None)
def _boundary_fn(bits: int, kernel: bool):
    """Jitted fused-boundary entry, cached per (bits, path).  No output
    has the activation's shape and dtype, so ``x`` is not donated: XLA
    could not reuse its buffer."""
    def f(x, centers):
        if kernel:
            return fused_boundary(x, centers, bits)
        return ref.fused_boundary_ref(x, centers, bits)
    return jax.jit(f)


def boundary_pass(x, centers, bits: int = 8, use_kernel: bool = True):
    """Single-pass fused boundary hop: x (B,S,D), centers (L,D) ->
    (payload, scale, zp, feat, sep, best, sims).  One HBM read of ``x``
    produces the wire packet fields *and* the semantic-probe outputs."""
    kernel = _count("boundary", bits, use_kernel and bits in KERNEL_BITS
                    and jax.default_backend() == "tpu")
    return _boundary_fn(int(bits), kernel)(x, centers)


# ------------------------------------------- trace-safe wire entry points
def wire_quantize(x, bits: int):
    """Shared wire quantize entry: Pallas kernel on TPU, exact jnp
    reference elsewhere.  Plain function — safe to trace inside
    ``shard_map``/``jit`` regions on any backend (interpret-mode Pallas
    cannot compile there), so the runtime, the SPMD pipeline, and the
    bench all measure the same code path."""
    if jax.default_backend() == "tpu" and bits in KERNEL_BITS:
        return uaq_quantize(x, bits)
    return ref.uaq_quantize_ref(x, bits)


def wire_dequantize(packed, scale, zp, bits: int, out_dtype=jnp.float32,
                    channels: Optional[int] = None):
    if jax.default_backend() == "tpu" and bits in KERNEL_BITS:
        return uaq_dequantize(packed, scale, zp, bits, out_dtype,
                              n=channels)
    return ref.uaq_dequantize_ref(packed, scale, zp, bits, out_dtype,
                                  n=channels)
