"""Seeded random weights, made on the device, for the system under test and
for the reference alike.

Every leaf is named (``groups/attn/wq``) and every value is a function of
the seed, the leaf's name and, for per-layer leaves, the layer index: a
uniform draw from 16 random bits, scaled to the leaf's standard deviation
in float32 and rounded once to the serving dtype.  Integer bits and
single IEEE operations make the values identical whether the whole stack
is made in one program (``make_segments``) or one layer at a time
(``layer_params``, for the reference), so the reference never needs a
weight the program made.

The tree mirrors the program's parameter layout: embedding, a stack of
layer groups with a leading layer axis, final norm and, where untied, the
head; the program's own split function places the leaves in segments
(``make_segments``).  The tests check the layout against the program's.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

SQRT3 = math.sqrt(3.0)


def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data for any non-negative integer seed (wider than
    32 bits too): two uint32 words, passed to jitted code as data so a new
    seed never recompiles."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      np.uint32)


def _leaf_key(key_data, name: str, layer=None):
    k = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    k = jax.random.fold_in(k, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return k if layer is None else jax.random.fold_in(k, layer)


def _draw(key, shape, std: float, mean: float, dtype):
    bits = jax.random.bits(key, shape, jnp.uint16).astype(jnp.float32)
    u = (bits - 32767.5) * jnp.float32(SQRT3 * std / 32768.0)
    return (u + jnp.float32(mean)).astype(dtype)


def layer_layout(cfg: dict):
    """``{name: (shape, std, mean, dtype)}`` of one layer's leaves."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    dt = jnp.dtype(cfg["dtype"])
    out = {
        "norm1/scale": ((d,), 0.1, 1.0, dt),
        "attn/wq": ((d, h * hd), 1 / math.sqrt(d), 0.0, dt),
        "attn/wk": ((d, kv * hd), 1 / math.sqrt(d), 0.0, dt),
        "attn/wv": ((d, kv * hd), 1 / math.sqrt(d), 0.0, dt),
        "attn/wo": ((h * hd, d), 1 / math.sqrt(h * hd), 0.0, dt),
        "norm2/scale": ((d,), 0.1, 1.0, dt),
    }
    e = cfg["num_local_experts"]
    if e:
        out.update({
            # the router is float32 in the program, as in the published model
            "moe/router": ((d, e), 1 / math.sqrt(d), 0.0, jnp.float32),
            "moe/w_gate": ((e, d, f), 1 / math.sqrt(d), 0.0, dt),
            "moe/w_up": ((e, d, f), 1 / math.sqrt(d), 0.0, dt),
            "moe/w_down": ((e, f, d), 1 / math.sqrt(f), 0.0, dt),
        })
    else:
        out.update({
            "mlp/w_gate": ((d, f), 1 / math.sqrt(d), 0.0, dt),
            "mlp/w_up": ((d, f), 1 / math.sqrt(d), 0.0, dt),
            "mlp/w_down": ((f, d), 1 / math.sqrt(f), 0.0, dt),
        })
    return out


def outer_layout(cfg: dict):
    """Leaves outside the layer stack."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg["dtype"])
    out = {"embed": ((v, d), 0.02, 0.0, dt),
           "final_norm/scale": ((d,), 0.1, 1.0, dt)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), 0.02, 0.0, dt)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, x in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def outer_params(key_data, cfg: dict) -> dict:
    return {name: _draw(_leaf_key(key_data, name), shape, std, mean, dt)
            for name, (shape, std, mean, dt) in outer_layout(cfg).items()}


def layer_params(key_data, cfg: dict, layer) -> dict:
    """One layer's leaves, flat by name (the reference's view)."""
    return {name: _draw(_leaf_key(key_data, "groups/" + name, layer),
                        shape, std, mean, dt)
            for name, (shape, std, mean, dt) in layer_layout(cfg).items()}


def _path_name(path) -> str:
    """``groups/attn/wq`` from a tree path; the pattern's tuple index and
    a list's segment index are not part of a leaf's name."""
    return "/".join(str(p.key) for p in path
                    if isinstance(p, jax.tree_util.DictKey))


def make_segments(key_data, cfg: dict, split):
    """The weights in the program's layout, as ``split`` arranges them.

    ``split`` is the program's own function from a whole tree to its
    segments (one-element list: the whole tree).  It is applied to a
    skeleton whose per-layer leaves are the layer indices, and each leaf
    of each segment is then drawn directly, one layer after another
    (``lax.map``): no whole tree is ever made and sliced, so only one
    layer's temporaries are live beside the finished segments."""
    n = cfg["num_hidden_layers"]
    layers = jnp.arange(n, dtype=jnp.uint32)
    outer = outer_layout(cfg)
    skeleton = _nest({name: jnp.zeros((), jnp.uint32) for name in outer})
    skeleton["groups"] = (_nest({name: layers
                                 for name in layer_layout(cfg)}),)
    per_layer = layer_layout(cfg)

    def fill(path, idx):
        name = _path_name(path)
        if name.startswith("groups/"):
            shape, std, mean, dt = per_layer[name[len("groups/"):]]
            return jax.lax.map(lambda i: _draw(
                _leaf_key(key_data, name, i), shape, std, mean, dt), idx)
        shape, std, mean, dt = outer[name]
        return _draw(_leaf_key(key_data, name), shape, std, mean, dt)

    return [jax.tree_util.tree_map_with_path(fill, seg)
            for seg in split(skeleton)]


def param_bytes(cfg: dict) -> int:
    per_layer = sum(int(np.prod(s)) * jnp.dtype(dt).itemsize
                    for s, _, _, dt in layer_layout(cfg).values())
    return (cfg["num_hidden_layers"] * per_layer
            + sum(int(np.prod(s)) * jnp.dtype(dt).itemsize
                  for s, _, _, dt in outer_layout(cfg).values()))
