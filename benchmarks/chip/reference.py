"""Plain float32 reference of the configurations, independent of the
program: ``jax.numpy`` only, every product at ``highest`` precision, the
whole model unsplit, weights regenerated from the seed one layer at a
time (``weights.layer_params``), so it fits beside nothing else.

The layer is the published one, as the configuration file states it:
RMSNorm, grouped-query attention with rotary embeddings (rotate-half,
the first and second half of each head paired) under a causal mask and
a local window, then either a SwiGLU MLP or a top-k mixture of experts
routed by a float32 softmax router whose top-k weights are renormalized.
The mixture is dropless: every token goes to each of its top-k experts.
The head reads the final RMSNorm of the last position.

``lower=True`` gives the control: the same computation with both inputs
of every weight product rounded to float8 (e4m3, one scale per weight
matrix and per token row), the precision below the configuration's
bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

E4M3_MAX = 240.0  # largest finite value with 4 exponent, 3 mantissa bits


def _f8(x, axis):
    """Round to 4 exponent and 3 mantissa bits (float8 e4m3) with one scale
    per slice along ``axis``.  ``reduce_precision`` and not a cast: XLA
    may fold a float32 -> float8 -> float32 pair of casts into nothing."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _mm(x, w, lower: bool):
    """x (..., K) @ w (K, N) in float32, or in float8 for the control."""
    w = w.astype(jnp.float32)
    if lower:
        x, w = _f8(x, -1), _f8(w, None)
    return jnp.einsum("...k,kn->...n", x, w)


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def rope(x, theta):
    """x: (S, heads, hd); rotate-half pairing of channel i with i + hd/2."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, x, p, lower):
    """One prompt: x (S, D) -> (S, D)."""
    S = x.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = rope(_mm(x, p["attn/wq"], lower).reshape(S, h, hd), cfg["rope_theta"])
    k = rope(_mm(x, p["attn/wk"], lower).reshape(S, kv, hd), cfg["rope_theta"])
    v = _mm(x, p["attn/wv"], lower).reshape(S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)  # query head j reads kv head j // rep
    v = jnp.repeat(v, h // kv, axis=1)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = (ki <= qi) & (ki > qi - cfg["sliding_window"])
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(S, h * hd), p["attn/wo"], lower)


def swiglu(x, wg, wu, wd, lower):
    return _mm(jax.nn.silu(_mm(x, wg, lower)) * _mm(x, wu, lower), wd, lower)


def experts(cfg, x, p, lower):
    """Dropless top-k mixture: x (T, D) -> ((T, D), assignments (T, E))."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["moe/router"].astype(jnp.float32), axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(ids, e) * top[..., None], axis=1)  # (T, E)

    def one(y, i):
        out = swiglu(x, p["moe/w_gate"][i], p["moe/w_up"][i],
                     p["moe/w_down"][i], lower)
        return y + jax.lax.dynamic_index_in_dim(gate, i, 1) * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(e))
    return y, jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"))
def _layer(h, p, cfg_items, lower):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, p["norm1/scale"], eps)
        rows = max(1, 4096 // h.shape[1])
        h = h + jax.lax.map(lambda xi: attention(cfg, xi, p, lower), x,
                            batch_size=rows)
        x = rms_norm(h, p["norm2/scale"], eps)
        n, S, D = x.shape
        if cfg["num_local_experts"]:
            y, hits = experts(cfg, x.reshape(n * S, D), p, lower)
            k = cfg["num_experts_per_tok"]
            last = jax.nn.softmax(x[:, -1] @ p["moe/router"], axis=-1)
            top, _ = jax.lax.top_k(last, k + 1)
            return (h + y.reshape(n, S, D),
                    jnp.sum(hits.reshape(n, S, -1), axis=1),
                    top[:, k - 1] - top[:, k])
        y = swiglu(x, p["mlp/w_gate"], p["mlp/w_up"], p["mlp/w_down"], lower)
        return h + y, jnp.zeros((n, 0), jnp.int32), jnp.full((n,), jnp.inf)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lower"))
def _head(h, outer, cfg_items, lower):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h[:, -1], outer["final_norm/scale"],
                     cfg["rms_norm_eps"])
        head = outer["lm_head"] if "lm_head" in outer else outer["embed"].T
        return _mm(x, head, lower)


def _hashable(cfg: dict):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "vocab_size", "sliding_window", "rms_norm_eps", "rope_theta",
            "tie_word_embeddings", "num_local_experts",
            "num_experts_per_tok", "dtype")
    return tuple((k, cfg[k]) for k in keys)


class Outputs(NamedTuple):
    logits: np.ndarray  # (n, V) float32, the last position
    feat: np.ndarray    # (n, D) mean hidden state after the first cut layers
    loads: np.ndarray   # (layers, n, E) tokens routed to each expert
    margin: np.ndarray  # (n,) least router margin of the last token: the
    #                     k-th expert's probability less the (k+1)-th's,
    #                     over the layers (inf without experts)


def forward(cfg: dict, key_data, tokens: np.ndarray, cut: int,
            lower: bool = False, block: int = 0) -> Outputs:
    """The reference's answers for ``tokens`` (n, S).  Each layer's
    weights are made once and applied to ``block`` prompts at a time (0:
    all at once)."""
    items = _hashable(cfg)
    make_layer = jax.jit(lambda k, i: W.layer_params(k, cfg, i))
    outer = jax.jit(lambda k: W.outer_params(k, cfg))(key_data)
    block = block or len(tokens)
    hs = [jnp.take(outer["embed"], jnp.asarray(tokens[i:i + block]), axis=0
                   ).astype(jnp.float32)
          for i in range(0, len(tokens), block)]
    feats, loads, margin = [], [], np.full(len(tokens), np.inf)
    for i in range(cfg["num_hidden_layers"]):
        if i == cut:
            feats = [np.asarray(jnp.mean(h, axis=1)) for h in hs]
        p = make_layer(key_data, np.uint32(i))
        outs = [_layer(h, p, items, lower) for h in hs]
        hs = [o[0] for o in outs]
        loads.append(np.concatenate([np.asarray(o[1]) for o in outs]))
        margin = np.minimum(margin, np.concatenate(
            [np.asarray(o[2]) for o in outs]))
        del p
    logits = np.concatenate([np.asarray(_head(h, outer, items, lower))
                             for h in hs])
    return Outputs(logits, np.concatenate(feats), np.stack(loads), margin)
