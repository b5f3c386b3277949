"""The fused flash-attention kernel (``kernels/attention.py``) against its
jnp reference in interpret mode, the dispatch in ``layers.attention_full``
that decides which of the two a layer runs, its path counter, and the
kernel's custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention as FA
from repro.kernels import ref
from repro.kernels.ops import PATHS
from repro.models import layers as L
from repro.models.config import LayerSpec, ModelConfig
from repro.models.shardctx import activation_sharding


def qkv(S, H, KV, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (1, S, n, hd)).astype(dtype)
                 for k, n in zip(ks, (H, KV, KV)))


def f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", ["causal", "local"])
@pytest.mark.parametrize("S", [128, 256, 512])
@pytest.mark.parametrize("hd", [120, 128])
@pytest.mark.parametrize("heads", [(4, 1), (2, 2)], ids=["gqa4", "mha"])
def test_kernel_matches_reference(heads, hd, S, window, dtype,
                                  monkeypatch):
    H, KV = heads
    # a window >= S is plain causal; a local one not aligned to a block
    W = S + 64 if window == "causal" else S // 4 + 3
    q, k, v = qkv(S, H, KV, hd, dtype)
    scale = 1.0 / np.sqrt(hd)
    # 4 query blocks, 2 key blocks of 4 chunks each: key blocks skipped,
    # whole, on the diagonal and on the window's edge
    for name, n in (("BLOCK_Q", 4), ("BLOCK_KV", 2), ("BLOCK_C", 8)):
        monkeypatch.setattr(FA, name, S // n)
    assert FA.blocks(S) == (S // 4, S // 2, S // 8)
    got = f32(FA.flash_attention(q, k, v, scale, W, interpret=True))
    want = f32(ref.causal_attention_ref(q, k, v, scale, W))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    # bf16: the kernel keeps scores in f32 where the reference rounds
    # them to bf16, so it lies nearer the f32 result than the reference
    exact = f32(ref.causal_attention_ref(*(a.astype(jnp.float32)
                                           for a in (q, k, v)), scale, W))
    top = np.abs(exact).max()
    assert np.abs(got - exact).max() <= 4e-3 * top
    assert np.abs(got - want).max() <= 1e-2 * top


def test_default_blocks_and_window_none():
    q, k, v = qkv(256, 4, 1, 120, jnp.float32, seed=1)
    got = FA.flash_attention(q, k, v, 0.1, None, interpret=True)
    want = ref.causal_attention_ref(q, k, v, 0.1, None)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert FA.blocks(128) == (128, 128, 128)
    assert FA.blocks(2048) == (FA.BLOCK_Q, FA.BLOCK_KV, FA.BLOCK_C)


def _cfg(**kw):
    base = dict(name="t", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=1, head_dim=16, d_ff=128,
                vocab_size=100, sliding_window=4096)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch as a TPU backend would see it (nothing is run)."""
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")


def test_dispatch_cpu_takes_reference():
    assert jax.default_backend() == "cpu"
    assert L.attention_path(_cfg(), LayerSpec(attn_kind="local"), 2048) \
        == "ref"


@pytest.mark.parametrize("kind", ["global", "local"])
def test_dispatch_tpu_takes_kernel(on_tpu, kind):
    spec = LayerSpec(attn_kind=kind)
    assert L.attention_path(_cfg(), spec, 2048) == "pallas"
    assert L.attention_path(_cfg(), spec, 128) == "pallas"


@pytest.mark.parametrize("why,cfg,spec,S", [
    ("softcap", dict(attn_logit_softcap=50.0), "global", 2048),
    ("bidirectional", dict(causal=False), "global", 2048),
    ("chunked", dict(attn_chunk=512), "chunked", 2048),
    ("ragged", {}, "global", 600),
])
def test_dispatch_tpu_keeps_reference(on_tpu, why, cfg, spec, S):
    assert L.attention_path(_cfg(**cfg), LayerSpec(attn_kind=spec), S) \
        == "ref", why


def test_dispatch_sharding_context_takes_reference(on_tpu):
    with activation_sharding({}):
        assert L.attention_path(_cfg(), LayerSpec(), 2048) == "ref"


def _layer(cfg, S, seed=2):
    p = L.init_attention(cfg, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (1, S, cfg.d_model)) * 0.5
    return p, x


def _jaxpr(cfg, spec, p, x):
    return str(jax.make_jaxpr(
        lambda p, x: L.attention_full(p, x, cfg, spec)[0])(p, x))


@pytest.mark.parametrize("S", [256, 2 * L.Q_CHUNK])
def test_reference_path_unchanged_off_kernel(S, monkeypatch):
    """CPU, and a TPU program under a sharding context (the dry-run),
    trace the same query-chunked jnp program, with no Pallas call."""
    cfg, spec = _cfg(), LayerSpec(attn_kind="local")
    p, x = _layer(cfg, S)
    cpu = _jaxpr(cfg, spec, p, x)
    assert "pallas_call" not in cpu
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    with activation_sharding({}):
        assert _jaxpr(cfg, spec, p, x) == cpu


def test_reference_path_is_the_reference_math():
    cfg, spec = _cfg(), LayerSpec(attn_kind="global")
    p, x = _layer(cfg, 256)
    q, k, v = L._qkv(p, x, cfg, jnp.arange(256)[None])
    want = ref.causal_attention_ref(q, k, v, 1.0 / np.sqrt(16)) \
        .reshape(1, 256, -1) @ p["wo"]
    got, _ = L.attention_full(p, x, cfg, spec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_path_counter_by_sequence_length(monkeypatch):
    cfg, spec = _cfg(), LayerSpec(attn_kind="local")
    p, x = _layer(cfg, 128)
    before = PATHS.copy()
    ref_out, _ = L.attention_full(p, x, cfg, spec)
    monkeypatch.setattr(L, "attention_path", lambda *a: "interpret")
    got, _ = L.attention_full(p, x, cfg, spec)
    jaxpr = _jaxpr(cfg, spec, p, x)
    assert FA.NAME in jaxpr
    new = PATHS - before
    assert new == {("attention", 128, "ref"): 1,
                   ("attention", 128, "interpret"): 2}
    np.testing.assert_allclose(got, ref_out, rtol=1e-4, atol=1e-5)


def test_custom_vjp_matches_reference_gradient(monkeypatch):
    cfg, spec = _cfg(sliding_window=40), LayerSpec(attn_kind="local")
    p, x = _layer(cfg, 128, seed=5)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, 128, cfg.d_model))

    def loss(p, x):
        return jnp.sum(L.attention_full(p, x, cfg, spec)[0] * w)

    want = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(L, "attention_path", lambda *a: "interpret")
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

