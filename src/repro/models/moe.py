"""Mixture-of-Experts FFN: router, top-k gates, and two dispatches.

Dropless (``expert_path`` "pallas"): every assignment is computed.  The
T*k assignments are counting-sorted by expert into a row buffer in which
each expert's rows start on a row block of the grouped-matmul kernel
(``kernels/grouped_matmul.py``); the kernel runs gate/up -> act * up and
then down over each expert's own rows only, and the rows are gathered
back and combined with the gates in float32.  A layer takes it when no
assignment can be dropped (``capacity(T) >= T``: the configuration
states dropless routing, or its capacity admits every token) in a TPU
program that no activation-sharding context partitions.

Capacity (``expert_path`` "ref"): the classic dispatch.  It avoids the
(T, E, C) one-hot blow-up: slots are computed with a running per-expert
cumsum, tokens are scattered into a (G, E, C+1, D) buffer (overflow tokens
land in the sacrificial last slot), expert FFNs run as one batched einsum
over E, and results are gathered back and gate-combined.  A dropless
layer off the kernel (CPU, the dry-run) runs it at capacity T: the same
mathematics, padded.

All capacity ops are explicitly G-batched (no vmap) so the launcher's
activation sharding constraints apply: token groups G shard over the data
axes, the expert FFN dim F over the model axis — the buffers stay
O(tokens/device).

Each layer's per-expert assignment counts, (E,) int32, go to the
innermost open ``collect_loads`` (``core/collab.py`` returns them from
its segment programs); each trace counts ("experts", T, path) in
``ops.PATHS``.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.kernels import grouped_matmul as GM
from repro.kernels.ops import PATHS
from repro.models.config import ModelConfig
from repro.models.shardctx import _specs, constrain

_COLLECTORS: list = []  # open ``collect_loads`` lists, innermost last


@contextlib.contextmanager
def collect_loads():
    """Collect the (E,) per-expert assignment counts of the expert layers
    traced inside, in order.  Open it in the trace that computes them: a
    count made inside a ``lax.scan`` body is collected inside that body."""
    got: list = []
    _COLLECTORS.append(got)
    try:
        yield got
    finally:
        _COLLECTORS.pop()


def record_loads(loads) -> None:
    if _COLLECTORS:
        _COLLECTORS[-1].append(loads)


def init_moe(cfg: ModelConfig, key, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(k1, (d, e)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (e, d, f)) * s).astype(dtype),
        "w_up": (jax.random.normal(k3, (e, d, f)) * s).astype(dtype),
        "w_down": (jax.random.normal(k4, (e, f, d)) * so).astype(dtype),
    }
    if cfg.shared_expert:
        ks = jax.random.split(k5, 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(ks[0], (d, f)) * s).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (d, f)) * s).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (f, d)) * so).astype(dtype),
        }
    return p


def capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    """Slots per expert and token group: every token under dropless
    routing, else the capacity factor's share."""
    if cfg.dropless:
        return tokens_per_group
    c = int(math.ceil(tokens_per_group * cfg.experts_per_token
                      * cfg.capacity_factor / cfg.num_experts))
    return max(4, min(c, tokens_per_group * cfg.experts_per_token))


def expert_path(cfg: ModelConfig, T: int) -> str:
    """Which dispatch ``moe_ffn`` runs for groups of ``T`` tokens, as
    ``ops.PATHS`` labels it: "pallas", the dropless grouped-matmul path,
    where no assignment can be dropped in a TPU program that no
    activation-sharding context partitions (a Pallas call cannot be);
    else "ref", the capacity dispatch."""
    kernel = (capacity(T, cfg) >= T and jax.default_backend() == "tpu"
              and _specs() is None)
    return "pallas" if kernel else "ref"


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_ffn(params, x, cfg: ModelConfig):
    """x: (G, T, D) token groups.  Returns (y, aux_loss).  The expert
    weights may be ``grouped_matmul.Slab``s: one layer of a stack."""
    G, T, D = x.shape
    k, E = cfg.experts_per_token, cfg.num_experts
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu

    # a float32 router at full precision: a TPU's default f32 product
    # rounds its inputs to bf16, and near-tied experts would flip
    logits = jnp.dot(x.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)  # (G,T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)  # (G,T,k)
    gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=2), axis=(0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce / k)

    path = expert_path(cfg, T)
    PATHS["experts", T, path] += 1
    if path == "ref":
        whole = {**params, **{w: params[w].get() for w in EXPERT_WEIGHTS
                              if isinstance(params[w], GM.Slab)}}
        y, loads = _capacity_ffn(whole, x, gates.astype(x.dtype), ids, cfg,
                                 act, capacity(T, cfg))
    else:
        y, loads = _dropless_ffn(params, x, gates, ids, act,
                                 path == "interpret")
    record_loads(loads)

    if cfg.shared_expert:
        sh = params["shared"]
        y = y + (act(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]
    return y, aux


def _dropless_ffn(params, x, gates, ids, act, interpret: bool):
    """Every assignment through the grouped-matmul kernel; gates (G,T,k)
    float32.  Returns (y, per-expert assignment counts)."""
    G, T, D = x.shape
    k, E = ids.shape[-1], params["router"].shape[1]
    n = G * T * k
    flat = ids.reshape(n)
    # counting sort by expert: each assignment's rank among its expert's,
    # in token order, and each expert's rows padded to whole row blocks
    oh = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    sizes = jnp.sum(oh, axis=0)
    rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0), flat[:, None],
                               axis=1)[:, 0] - 1
    bm = GM.BLOCK_M
    padded = GM.padded(sizes, bm)
    row = (jnp.cumsum(padded) - padded)[flat] + rank  # (n,)
    m = -(-(n + E * (bm - 1)) // bm) * bm  # the most rows padding can need
    # the token of each row; the sentinel row G*T gathers zeros
    tok = jnp.full((m,), G * T, jnp.int32).at[row].set(
        jnp.arange(n, dtype=jnp.int32) // k)
    xs = jnp.concatenate([x.reshape(G * T, D), jnp.zeros((1, D), x.dtype)])
    h = GM.grouped_matmul(xs[tok], (params["w_gate"], params["w_up"]),
                          padded, act, interpret)
    yr = GM.grouped_matmul(h, params["w_down"], padded,
                           interpret=interpret)
    out = yr[row].reshape(G, T, k, D).astype(jnp.float32)
    y = jnp.sum(out * gates[..., None], axis=2)
    return y.astype(x.dtype), sizes


def _capacity_ffn(params, x, gates, ids, cfg: ModelConfig, act, cap: int):
    """The capacity dispatch at ``cap`` slots per expert and group; gates
    (G,T,k) in ``x``'s dtype.  Returns (y, per-expert assignment
    counts, dropped ones included)."""
    G, T, D = x.shape
    k, E = cfg.experts_per_token, cfg.num_experts

    # slots: running per-(group, expert) assignment count
    flat_ids = ids.reshape(G, T * k)
    oh = constrain(jax.nn.one_hot(flat_ids, E, dtype=jnp.int32), "moe_oh")
    slot = jnp.cumsum(oh, axis=1) - 1  # (G,Tk,E)
    slot = jnp.take_along_axis(slot, flat_ids[..., None], axis=2)[..., 0]
    slot = jnp.where(slot < cap, slot, cap)  # overflow -> sacrificial slot

    gi = jnp.broadcast_to(jnp.arange(G)[:, None], (G, T * k))
    tok = jnp.broadcast_to(jnp.repeat(jnp.arange(T), k)[None], (G, T * k))
    # dispatch via an int32 token-index map + gather instead of scattering
    # the activations directly: JAX upcasts bf16 scatter-adds to f32, which
    # made the (G,E,C,D) buffers the dominant HBM traffic of MoE prefill
    # (§Perf pair 3 it4).  Slots are unique per (g,e) so set() semantics
    # match add(); the sentinel row T gathers zeros.
    tok_map = jnp.full((G, E, cap + 1), T, jnp.int32)
    tok_map = tok_map.at[gi, flat_ids, slot].set(tok)
    x_pad = jnp.concatenate([x, jnp.zeros((G, 1, D), x.dtype)], axis=1)
    buf = x_pad[jnp.arange(G)[:, None, None], tok_map]  # (G,E,C+1,D) gather
    buf = constrain(buf, "moe_buf")

    # expert FFN (active FLOPs only: G * E * cap * D * F)
    h = constrain(jnp.einsum("gecd,edf->gecf", buf, params["w_gate"]), "moe_h")
    u = constrain(jnp.einsum("gecd,edf->gecf", buf, params["w_up"]), "moe_h")
    yb = jnp.einsum("gecf,efd->gecd", (act(h) * u).astype(x.dtype),
                    params["w_down"])
    yb = constrain(yb.astype(x.dtype), "moe_buf")

    # gather back + gate combine; overflow slot contributes zero via mask
    out_k = yb[gi, flat_ids, slot]  # (G,Tk,D)
    valid = (slot < cap).astype(gates.dtype).reshape(G, T, k)
    y = jnp.sum(out_k.reshape(G, T, k, D) * (gates * valid)[..., None], axis=2)
    y = constrain(y, "hidden")
    return y, jnp.sum(oh, axis=(0, 1))
