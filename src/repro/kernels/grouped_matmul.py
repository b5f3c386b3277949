"""Pallas TPU kernel: grouped matrix product over the experts a layer holds.

``grouped_matmul(lhs, rhs, group_sizes)`` computes, for each group ``g``,
the rows of ``lhs`` (M, K) that belong to it times ``rhs[g]`` (K, N):
the rows are sorted by group, group ``g`` owning the ``group_sizes[g]``
rows after those of the groups before it.  A dropless expert layer
(``models/moe.py``) runs its routed rows through it, so that each
expert multiplies only the tokens routed to it.

Grid (N blocks, row-block visits), both sequential.  A visit is one
(group, row block) pair: every row block a group touches, groups in
order, so a block that two groups share is visited once for each of
them, consecutively, and each visit stores only its own group's rows
(the layout of the megablox ``gmm`` that ships with JAX).  The number of
visits is known only on the device; the grid takes it as a traced size,
and the index maps read each visit's row block from scalar-prefetched
tables.  Each step multiplies a (block_m, K) row block by a (K, block_n)
block of the group's matrix: K is not split, so a group's weight block
stays in VMEM across all of its row blocks and is read from HBM once per
N block.

The weight blocks are copied by hand into two VMEM slots per matrix, in
the order the grid uses them: the (group, N block) pairs of the first N
block, then those of the next.  At a pair's first visit the kernel waits
for its block and starts the copy of the next pair's into the other
slot, so each copy has the whole of a group's visits to land in, not
the one step that the automatic pipeline gives it.  The row block and
the output stay on the automatic pipeline.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NAME = "coach_grouped_matmul"  # the pallas_call's name, as traced
# row block and N block (PERF.md: the tile sweep at Mixtral's shapes on a
# v5e); K is never split
BLOCK_M, BLOCK_N = 128, 1024
VMEM_LIMIT = 96 * 2**20  # two (K, block_n) slots of each matrix, rows


class Slab(NamedTuple):
    """Layer ``index`` (a traced int) of ``stack`` (L, G, K, N)."""
    stack: jax.Array
    index: jax.Array

    def get(self):
        return self.stack[self.index]


def block_n(N: int) -> int:
    """The N block: ``BLOCK_N`` where it divides ``N``, else ``N``."""
    return BLOCK_N if N % BLOCK_N == 0 else N


def padded(sizes, bm: Optional[int] = None):
    """Each group's rows rounded up to whole row blocks (``BLOCK_M``), as
    the dropless layer lays them out, so that no two groups share one."""
    bm = BLOCK_M if bm is None else bm
    return (sizes + bm - 1) // bm * bm


class Visits(NamedTuple):
    """The visit tables, padded to the most visits ``m`` rows can need,
    ``m // bm + G - 1``.  The groups with rows are the experts a visit
    can name, numbered in order."""
    offsets: jax.Array   # (G + 1,) each group's first row, then M
    order: jax.Array     # (G,) the group of each expert; the rest 0
    expert: jax.Array    # (V,) the expert of each visit
    tile: jax.Array      # (V,) the row block of each visit
    experts: jax.Array   # (1,) how many groups have rows
    n: jax.Array         # () how many visits there are


def visits(group_sizes, m: int, bm: int) -> Visits:
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // bm
    count = jnp.where(group_sizes > 0, (ends - 1) // bm - first + 1, 0)
    upto = jnp.cumsum(count)
    v = jnp.arange(m // bm + G - 1, dtype=jnp.int32)
    gid = jnp.minimum(jnp.searchsorted(upto, v, side="right"), G - 1)
    tile = first[gid] + v - (upto[gid] - count[gid])
    used = group_sizes > 0
    rank = jnp.cumsum(used) - 1
    order = jnp.zeros(G, jnp.int32).at[jnp.where(used, rank, G)].set(
        jnp.arange(G, dtype=jnp.int32), mode="drop")
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    i32 = lambda a: a.astype(jnp.int32)
    return Visits(i32(offsets), order, i32(jnp.maximum(rank[gid], 0)),
                  i32(jnp.clip(tile, 0, m // bm - 1)),
                  i32(jnp.sum(used, keepdims=True)), upto[-1])


def fetch_schedule(group_sizes, bm: Optional[int] = None):
    """On the host, for the kernel's ``group_sizes`` (each group's rows):
    (rows it computes, share of its weight-block copies started two or
    more visits before their first use).  A copy starts at the first
    visit of the pair before it, so its lead is that group's visits; the
    chain runs on from one N block into the next, and the first copy of
    all, which nothing precedes, is left out."""
    bm = BLOCK_M if bm is None else bm
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    count = np.where(sizes > 0, (ends - 1) // bm - (ends - sizes) // bm + 1,
                     0)
    lead = count[count > 0]
    if not lead.size:
        return 0, 0.0
    # each expert's copy is started at its predecessor's first visit
    return int(count.sum()) * bm, float(np.mean(np.roll(lead, 1) >= 2))


def _kernel(offsets, order, expert, tile, experts, layer, lhs_ref, *refs,
            bm: int, bn: int, act):
    r = (len(refs) - 2) // 2
    w_hbm, out_ref, slots, sems = refs[:r], refs[r], refs[r + 1:-1], refs[-1]
    n, v = pl.program_id(0), pl.program_id(1)
    e = experts[0]
    p = n * e + expert[v]      # this visit's (group, N block) pair, in order
    slot = p % 2

    def copies(q, s):
        cols = pl.ds(pl.multiple_of(q // e * bn, bn), bn)
        return [pltpu.make_async_copy(w.at[layer[0], order[q % e], :, cols],
                                      buf.at[s], sems.at[i, s])
                for i, (w, buf) in enumerate(zip(w_hbm, slots))]

    @pl.when((v == 0) | (expert[v] != expert[jnp.maximum(v - 1, 0)]))
    def _():
        @pl.when(p == 0)
        def _():
            for c in copies(0, 0):
                c.start()

        for c in copies(p, slot):
            c.wait()

        @pl.when(p + 1 < pl.num_programs(0) * e)
        def _():
            for c in copies(p + 1, 1 - slot):
                c.start()

    x = lhs_ref[...]
    acc = [jnp.dot(x, buf[slot], preferred_element_type=jnp.float32)
           for buf in slots]
    y = act(acc[0]) * acc[1] if len(acc) == 2 else acc[0]
    g = order[expert[v]]
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
    vis = visits(group_sizes.astype(jnp.int32), m, bm)
    out = pl.pallas_call(
        functools.partial(_kernel, bm=bm, bn=bn, act=act),
        out_shape=jax.ShapeDtypeStruct((m, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(N // bn, vis.n),
            in_specs=[pl.BlockSpec((bm, K),
                                   lambda n, v, o, g, e, t, *_: (t[v], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in rhs],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda n, v, o, g, e, t, *_: (t[v], n)),
            scratch_shapes=[pltpu.VMEM((2, K, bn), w.dtype) for w in rhs]
            + [pltpu.SemaphoreType.DMA((len(rhs), 2))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=NAME,
    )(vis.offsets, vis.order, vis.expert, vis.tile, vis.experts, layer,
      lhs, *rhs)
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
