"""Pallas TPU kernel: causal flash attention with grouped KV heads.

The served segments run full-sequence causal attention at S = 2048.  The
jnp path (``models.layers._attend``) materializes each query chunk's
(KV, rep, Q, S) scores in HBM and reads them back twice (softmax, then
PV).  This kernel keeps every score in VMEM:

  grid (B, KV heads, Q blocks, KV blocks), the last one sequential; per
  step the ``rep`` query heads that share one KV head form one
  (rep * query block, hd) tile, so each K/V tile is read once for all
  of them.  A loop over the key block's chunks folds one
  (rep * query block, chunk) f32 score tile at a time into a running
  max / sum / accumulator (online softmax).  Keys outside a query
  block's view (above the diagonal, or wholly before its local window)
  are neither computed nor, at the grain of a key block, loaded: the
  loop's bounds leave their chunks out and the index map repeats the
  last block fetched.  Only chunks that cross the diagonal or the
  window's edge are masked.

Queries are laid out as (B, KV, S * rep, hd), row ``s * rep + r`` holding
head ``g * rep + r`` at position ``s``; keys as (B, KV, hd, S), so the
score matmul takes them untransposed; values as (B, KV, S, hd): one
transpose in HBM of each projection.  Mathematics of the reference
(``ref.causal_attention_ref``): scores scaled by ``scale``, keys
``q - window < k <= q`` visible, f32 softmax, probabilities cast to the
value dtype before PV, f32 accumulation.

``causal_attention`` is the one entry point; its ``jax.custom_vjp``
differentiates through the reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NAME = "coach_flash_attention"  # the pallas_call's name, as traced
# largest query block, key block fetched at once, and key chunk computed
# at once (PERF.md: the block sweep at S = 2048 on a v5e)
BLOCK_Q, BLOCK_KV, BLOCK_C = 256, 2048, 1024
VMEM_LIMIT = 64 * 2**20  # room for the sweep's largest score tiles
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


def blocks(S: int):
    """(query block, key block, key chunk) for sequence length ``S``."""
    bkv = min(BLOCK_KV, S)
    return min(BLOCK_Q, S), bkv, min(BLOCK_C, bkv)


def tiles(S: int) -> bool:
    """Whether ``blocks(S)`` tile a sequence of length ``S``."""
    bq, bkv, bc = blocks(S)
    return S % bq == 0 and S % bkv == 0 and bkv % bc == 0


def _kv_range(i, bq: int, bkv: int, window: int):
    """First and last KV block that query block ``i`` sees."""
    lo = jnp.maximum(i * bq - window + 1, 0) // bkv
    return lo, (i * bq + bq - 1) // bkv


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: int, seq_len: int, rep: int, bq: int,
            bkv: int, bc: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(c, k0, masked: bool):
        keys = pl.ds(pl.multiple_of(c * bc, bc), bc) if bc < bkv \
            else slice(None)  # a lane slice must start at a multiple of 128
        s = jnp.dot(q_ref[...], k_ref[:, keys],
                    preferred_element_type=jnp.float32) * scale
        if masked:
            # positions as a column and a row, compared by broadcasting
            qpos = q0 + lax.broadcasted_iota(
                jnp.int32, (s.shape[0], 1), 0) // rep
            kpos = k0 + lax.broadcasted_iota(jnp.int32, (1, bc), 1)
            seen = kpos <= qpos
            if window < seq_len:
                seen &= kpos > qpos - window
            s = jnp.where(seen, s, MASK)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[keys, :],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def body(c, carry):
        k0 = j * bkv + c * bc
        whole = (k0 + bc - 1 <= q0) & (k0 > q1 - window)
        pl.when(whole)(functools.partial(chunk, c, k0, False))
        pl.when(jnp.logical_not(whole))(functools.partial(chunk, c, k0, True))
        return carry

    # the chunks of this key block that the query block sees, in order:
    # whole ones unmasked, those on the diagonal or the window's edge
    # masked
    q0, q1 = i * bq, i * bq + bq - 1
    nc = bkv // bc
    first = jnp.maximum(q0 - window + 1, 0) // bc - j * nc
    last = q1 // bc - j * nc
    lax.fori_loop(jnp.maximum(first, 0), jnp.minimum(last, nc - 1) + 1,
                  body, None)

    @pl.when(j == seq_len // bkv - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention(q, k, v, scale: float, window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q (B, S, H, hd), k / v (B, S, KV, hd) -> (B, S, H, hd), causal,
    keys ``q - window < k <= q`` (``None``: every earlier key), in the
    blocks ``blocks(S)`` picks; they must tile ``S``."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    assert H == KV * rep, (H, KV)
    window = S if window is None else min(int(window), S)
    bq, bkv, bc = blocks(S)
    assert tiles(S), (S, bq, bkv, bc)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qg = q.reshape(B, S, KV, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, S * rep, hd)
    kt, vt = k.transpose(0, 2, 3, 1), v.transpose(0, 2, 1, 3)
    rows = rep * bq

    def kv_block(i, j):
        lo, hi = _kv_range(i, bq, bkv, window)
        return jnp.clip(j, lo, hi)

    q_spec = pl.BlockSpec((None, None, rows, hd), lambda b, g, i, j: (b, g, i, 0))
    k_spec = pl.BlockSpec((None, None, hd, bkv),
                          lambda b, g, i, j: (b, g, 0, kv_block(i, j)))
    v_spec = pl.BlockSpec((None, None, bkv, hd),
                          lambda b, g, i, j: (b, g, kv_block(i, j), 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), window=window,
                          seq_len=S, rep=rep, bq=bq, bkv=bkv, bc=bc),
        grid=(B, KV, S // bq, S // bkv),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(qg, kt, vt)
    return out.reshape(B, KV, S, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention(q, k, v, scale: float, window: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """The kernel forward; the gradient is the reference's, recomputed."""
    return flash_attention(q, k, v, scale, window, interpret)


def _fwd(q, k, v, scale, window, interpret):
    return flash_attention(q, k, v, scale, window, interpret), (q, k, v)


def _bwd(scale, window, interpret, res, g):
    _, vjp = jax.vjp(
        lambda *a: ref.causal_attention_ref(*a, scale, window), *res)
    return vjp(g)


causal_attention.defvjp(_fwd, _bwd)
