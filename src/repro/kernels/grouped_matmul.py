"""Pallas TPU kernel: grouped matrix product over the experts a layer holds.

``grouped_matmul(lhs, rhs, group_sizes)`` computes, for each group ``g``,
the rows of ``lhs`` (M, K) that belong to it times ``rhs[g]`` (K, N):
the rows are sorted by group, group ``g`` owning the ``group_sizes[g]``
rows after those of the groups before it.  A dropless expert layer
(``models/moe.py``) runs its routed rows through it, so that each
expert multiplies only the tokens routed to it.

Grid (N blocks, row-block visits), the second sequential.  A visit is
one (group, row block) pair: every row block a group touches, groups in
order, so a block that two groups share is visited once for each of
them, consecutively, and each visit stores only its own group's rows
(the layout of the megablox ``gmm`` that ships with JAX).  The number of
visits is known only on the device; the grid takes it as a traced size,
and the index maps read each visit's group and row block from
scalar-prefetched tables.  Each step multiplies a (block_m, K) row block
by a (K, block_n) block of the group's matrix: K is not split, so a
group's weight block stays in VMEM across all of its row blocks and is
read from HBM once per N block.

A matrix may be passed as one layer of a stack, ``Slab(stack, index)``
with ``stack`` (L, G, K, N): the kernel reads that layer's blocks in
place.  Inside a scan over stacked layers a sliced operand would be
copied whole before each call (2.8 GB a Mixtral layer); the slab is not.

A second matrix (``rhs`` a pair) gives the gated form that an expert's
first layer needs, ``act(lhs @ rhs[0]) * (lhs @ rhs[1])``, from one read
of each row block; both products accumulate in float32 and the output is
cast once.  Rows after the last group's are left undefined.

``grouped_matmul`` differentiates through the reference
(``ref.grouped_matmul_ref``), recomputed.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NAME = "coach_grouped_matmul"  # the pallas_call's name, as traced
# row block and N block (PERF.md: the tile sweep at Mixtral's shapes on a
# v5e); K is never split
BLOCK_M, BLOCK_N = 256, 1024
VMEM_LIMIT = 96 * 2**20  # a (K, block_n) block of each matrix, twice


class Slab(NamedTuple):
    """Layer ``index`` (a traced int) of ``stack`` (L, G, K, N)."""
    stack: jax.Array
    index: jax.Array

    def get(self):
        return self.stack[self.index]


def block_n(N: int) -> int:
    """The N block: ``BLOCK_N`` where it divides ``N``, else ``N``."""
    return BLOCK_N if N % BLOCK_N == 0 else N


def visits(group_sizes, m: int, bm: int):
    """(row offsets (G + 1,), group of each visit, row block of each
    visit, number of visits): the visit tables, padded to the most visits
    ``m`` rows can need, ``m // bm + G - 1``."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // bm
    count = jnp.where(group_sizes > 0, (ends - 1) // bm - first + 1, 0)
    upto = jnp.cumsum(count)
    v = jnp.arange(m // bm + G - 1, dtype=jnp.int32)
    gid = jnp.minimum(jnp.searchsorted(upto, v, side="right"), G - 1)
    tile = first[gid] + v - (upto[gid] - count[gid])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), gid.astype(jnp.int32),
            jnp.clip(tile, 0, m // bm - 1).astype(jnp.int32), upto[-1])


def _kernel(offsets, gid, tile, layer, lhs_ref, *refs, bm: int, act):
    *rhs_refs, out_ref = refs
    v = pl.program_id(1)
    x = lhs_ref[...]
    acc = [jnp.dot(x, r[...], preferred_element_type=jnp.float32)
           for r in rhs_refs]
    y = act(acc[0]) * acc[1] if len(acc) == 2 else acc[0]
    g = gid[v]
    row = tile[v] * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    out_ref[...] = jnp.where(mine, y, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def _grouped(lhs, rhs: tuple, layer, group_sizes, act, interpret: bool):
    """``rhs``: (L, G, K, N) stacks, each read at layer ``layer[0]``."""
    M, K = lhs.shape
    N = rhs[0].shape[3]
    bm, bn = BLOCK_M, block_n(N)
    pad = -M % bm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    m = M + pad
    offsets, gid, tile, n_visits = visits(group_sizes.astype(jnp.int32),
                                          m, bm)
    out = pl.pallas_call(
        functools.partial(_kernel, bm=bm, act=act),
        out_shape=jax.ShapeDtypeStruct((m, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // bn, n_visits),
            in_specs=[pl.BlockSpec((bm, K),
                                   lambda n, v, o, g, t, l: (t[v], 0))]
            + [pl.BlockSpec((None, None, K, bn),
                            lambda n, v, o, g, t, l: (l[0], g[v], 0, n))
               for _ in rhs],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda n, v, o, g, t, l: (t[v], n)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(offsets, gid, tile, layer, lhs, *rhs)
    return out[:M] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(lhs, rhs, layer, group_sizes, act, interpret):
    return _grouped(lhs, rhs, layer, group_sizes, act, interpret)


def _fwd(lhs, rhs, layer, group_sizes, act, interpret):
    return _grouped(lhs, rhs, layer, group_sizes, act, interpret), \
        (lhs, rhs, layer, group_sizes)


def _bwd(act, interpret, res, g):
    lhs, rhs, layer, sizes = res
    _, vjp = jax.vjp(lambda a, b: ref.grouped_matmul_ref(
        a, tuple(w[layer[0]] for w in b), sizes, act), lhs, rhs)
    return (*vjp(g), None, None)


_gmm.defvjp(_fwd, _bwd)


def grouped_matmul(lhs, rhs, group_sizes, act=jax.nn.silu,
                   interpret: Optional[bool] = None):
    """lhs (M, K) rows sorted by group, rhs a (G, K, N) matrix per group
    or a ``Slab`` of them, or a pair, group_sizes (G,) int -> (M, N) in
    ``lhs``'s dtype: each group's rows times its matrix, or for a pair
    ``act(lhs @ rhs[0]) * (lhs @ rhs[1])`` per group.  Rows after
    ``sum(group_sizes)`` are undefined.  The matrices of a pair are both
    whole or both slabs of one layer."""
    rhs = (rhs,) if isinstance(rhs, Slab) or not isinstance(
        rhs, (tuple, list)) else tuple(rhs)
    if isinstance(rhs[0], Slab):
        layer = rhs[0].index
        rhs = tuple(w.stack for w in rhs)
    else:
        layer = 0
        rhs = tuple(w[None] for w in rhs)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gmm(lhs, rhs, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
                group_sizes, act if len(rhs) == 2 else None, interpret)
