"""Core NN layers: norms, rotary embeddings (incl. M-RoPE), GQA attention
(global / sliding-window / chunked, softcap, qk-norm), and gated MLPs.

Pure JAX, explicit parameter pytrees (dicts).  Attention over long sequences
uses a query-chunked ``lax.scan`` so (S x S) score matrices are never
materialized — required for the 32k prefill shapes on the dry-run mesh.
Causal global / local attention on an unsharded TPU program takes the
fused flash kernel instead (``kernels.attention``; ``attention_path``).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import attention as FA
from repro.kernels.ops import PATHS
from repro.models.config import LayerSpec, ModelConfig
from repro.models.shardctx import _specs, constrain

# Query-chunk length for memory-efficient full-sequence attention.
Q_CHUNK = 1024


# --------------------------------------------------------------------------- norm
def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(x, params, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------- rope
def rope_angles(positions, head_dim: int, theta: float,
                mrope_sections: Optional[Tuple[int, int, int]] = None):
    """positions: (..., S) int32, or (3, ..., S) for M-RoPE.

    Returns cos, sin with shape (..., S, head_dim // 2), float32.
    """
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if mrope_sections is not None:
        # Each frequency index is driven by one of the (t, h, w) position
        # streams [arXiv:2409.12191].  Text-only inputs use identical streams.
        if positions.ndim == 2:  # plain (B,S) text positions -> broadcast
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        import numpy as np
        sec_id = jnp.asarray(np.repeat(np.arange(3), np.asarray(mrope_sections)))  # (half,)
        pos = jnp.take(positions, sec_id, axis=0)  # (half, ..., S)
        ang = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * inv_freq  # (...,S,half)
    else:
        ang = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, half) or (S, half)."""
    half = x.shape[-1] // 2
    if cos.ndim == 2:
        cos_, sin_ = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos_, sin_ = cos[:, :, None, :], sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos_ - x2 * sin_, x2 * cos_ + x1 * sin_], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------- attention
def init_attention(cfg: ModelConfig, key, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(k1, (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, kv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, kv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (h * hd, d)) * (1.0 / math.sqrt(h * hd))).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _qkv(params, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, h, hd)
    k = (x @ params["wk"]).reshape(B, S, kv, hd)
    v = (x @ params["wv"]).reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        cos, sin = rope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return (constrain(q, "q_heads"), constrain(k, "kv_heads"),
            constrain(v, "kv_heads"))


def _scores_mask(q_pos, k_pos, cfg: ModelConfig, spec: LayerSpec, causal: bool):
    """(Q, K) boolean mask from absolute positions."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    m = kp >= 0  # invalid (unwritten ring slots) carry negative positions
    if causal:
        m &= kp <= qp
    if spec.attn_kind == "local":
        m &= kp > qp - cfg.sliding_window
    elif spec.attn_kind == "chunked":
        m &= (kp // cfg.attn_chunk) == (qp // cfg.attn_chunk)
    return m


def _scale(cfg: ModelConfig, hd: int) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(hd)


def _attend(q, k, v, mask, cfg: ModelConfig):
    """q: (B,Q,H,hd)  k/v: (B,K,KV,hd)  mask: (Q,K) or (B,Q,K)."""
    B, Q, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    scale = _scale(cfg, hd)
    qr = q.reshape(B, Q, KV, rep, hd)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qr, k).astype(jnp.float32) * scale
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w, v)
    return constrain(out.reshape(B, Q, H * hd), "attn_out")


def attention_path(cfg: ModelConfig, spec: LayerSpec, S: int) -> str:
    """Which attention ``attention_full`` runs, as ``ops.PATHS`` labels it:
    "pallas", the fused flash kernel, for causal global / local attention
    without a logit softcap in a TPU program that no activation-sharding
    context partitions (a Pallas call cannot be), when the kernel's
    blocks tile ``S``; else "ref", the query-chunked jnp path."""
    fused = (jax.default_backend() == "tpu" and _specs() is None
             and cfg.causal and spec.attn_kind in ("global", "local")
             and not cfg.attn_logit_softcap
             and FA.tiles(S))
    return "pallas" if fused else "ref"


def attention_full(params, x, cfg: ModelConfig, spec: LayerSpec, positions=None):
    """Full-sequence attention (train / prefill): the flash kernel, or
    query-chunked over S (``attention_path``).  Each trace counts its path
    in ``ops.PATHS`` under ("attention", S, path)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    q, k, v = _qkv(params, x, cfg, positions)
    path = attention_path(cfg, spec, S)
    PATHS["attention", S, path] += 1
    if path != "ref":
        window = cfg.sliding_window if spec.attn_kind == "local" else None
        out = FA.causal_attention(q, k, v, _scale(cfg, q.shape[-1]), window,
                                  path == "interpret")
        return out.reshape(B, S, -1) @ params["wo"], (k, v)
    causal = cfg.causal
    kpos = jnp.arange(S, dtype=jnp.int32)

    if S <= Q_CHUNK:
        mask = _scores_mask(kpos, kpos, cfg, spec, causal)
        out = _attend(q, k, v, mask, cfg)
    else:
        assert S % Q_CHUNK == 0, f"S={S} not divisible by Q_CHUNK={Q_CHUNK}"
        n = S // Q_CHUNK
        qc = q.reshape(B, n, Q_CHUNK, *q.shape[2:]).transpose(1, 0, 2, 3, 4)

        def body(carry, inp):
            i, qi = inp
            qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK, dtype=jnp.int32)
            mask = _scores_mask(qpos, kpos, cfg, spec, causal)
            return carry, _attend(qi, k, v, mask, cfg)

        _, outs = lax.scan(body, None, (jnp.arange(n), qc))
        out = outs.transpose(1, 0, 2, 3).reshape(B, S, -1)
    return out @ params["wo"], (k, v)


# ------------------------------------------------------------------ KV cache utils
def cache_len(cfg: ModelConfig, spec: LayerSpec, max_seq: int) -> int:
    if spec.attn_kind == "local":
        return min(max_seq, cfg.sliding_window)
    if spec.attn_kind == "chunked":
        return min(max_seq, cfg.attn_chunk)
    return max_seq


def init_kv_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int,
                  dtype=jnp.float32):
    L = cache_len(cfg, spec, max_seq)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, L, kv, hd), dtype),
        "v": jnp.zeros((batch, L, kv, hd), dtype),
        # absolute position held by each slot; -1 => empty
        "pos": jnp.full((L,), -1, jnp.int32),
    }


def prefill_to_cache(cfg, spec, k, v, max_seq: int):
    """Convert full-sequence rope'd k/v (B,S,KV,hd) into a decode cache of
    length ``cache_len`` (ring layout: slot = pos % L)."""
    B, S, KV, hd = k.shape
    L = cache_len(cfg, spec, max_seq)
    if L == max_seq and S <= L:
        pad = L - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
        return {"k": kc, "v": vc, "pos": pos}
    # keep last L positions, ring-ordered
    start = S - L
    ppos = start + jnp.arange(L, dtype=jnp.int32)
    slots = ppos % L
    kc = jnp.zeros((B, L, KV, hd), k.dtype).at[:, slots].set(k[:, start:])
    vc = jnp.zeros((B, L, KV, hd), v.dtype).at[:, slots].set(v[:, start:])
    pos = jnp.zeros((L,), jnp.int32).at[slots].set(ppos)
    return {"k": kc, "v": vc, "pos": pos}


def attention_decode(params, x, cache, pos, cfg: ModelConfig, spec: LayerSpec):
    """One-token decode.  x: (B,1,D); pos: scalar int32 (position of x)."""
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos[None, None], (B, 1))
    q, k, v = _qkv(params, x, cfg, positions)  # (B,1,·,hd), rope'd at abs pos
    L = cache["k"].shape[1]
    slot = pos % L
    kc = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    vc = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    cpos = cache["pos"].at[slot].set(pos)
    mask = _scores_mask(positions[0], cpos, cfg, spec, causal=True)  # (1,L)
    out = _attend(q, kc, vc, mask, cfg)
    return out @ params["wo"], {"k": kc, "v": vc, "pos": cpos}


# --------------------------------------------------------------------------- MLP
def init_mlp(d: int, f: int, key, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * s).astype(dtype),
        "w_up": (jax.random.normal(k2, (d, f)) * s).astype(dtype),
        "w_down": (jax.random.normal(k3, (f, d)) * so).astype(dtype),
    }


def mlp(params, x, act: str = "silu"):
    a = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = constrain(a(x @ params["w_gate"]) * (x @ params["w_up"]), "ffn")
    return h @ params["w_down"]
