"""The grouped-matmul kernel (``kernels/grouped_matmul.py``) against a
plain per-group product in interpret mode; the dropless expert layer
(``models/moe.py``) against the float32 reference of the published
routing (``ref.moe_dropless_ref``); the capacity path at capacity T
against the dropless one; the dispatch in ``moe.expert_path`` and its
path counter; and a reduced Mixtral through ``CollabRuntime``, split
against unsplit, with the expert loads its segment programs return."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.collab import CollabRuntime
from repro.kernels import grouped_matmul as GM
from repro.kernels import ref
from repro.kernels.ops import PATHS
from repro.models import model as M
from repro.models import moe as MOE
from repro.models.shardctx import activation_sharding

D, F, T = 128, 256, 40


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 16 and N blocks of 128, so that small groups share
    and straddle blocks."""
    monkeypatch.setattr(GM, "BLOCK_M", 16)
    monkeypatch.setattr(GM, "BLOCK_N", 128)


def f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


# uneven groups with an empty and a single-row one, M not a multiple of
# the block (rows past the last group are undefined); one group in the
# middle of four; groups filling whole blocks.  Then, with each matrix a
# slab of a layer stack read at a layer other than 0: a one-visit group
# before a four-visit one and the reverse, empty groups between
# non-empty ones, and one group alone; with 2 or 3 N blocks, so that the
# weight copies run on from one N block into the next
@pytest.mark.parametrize("sizes,M,N,layer", [
    ((5, 0, 17, 1, 9), 35, 256, None),
    ((0, 0, 3, 0), 3, 256, None),
    ((16, 16, 16), 48, 256, None),
    ((7, 50), 57, 256, 1),
    ((50, 7), 57, 384, 2),
    ((9, 0, 0, 20, 0, 3), 32, 256, 1),
    ((0, 40, 0), 40, 384, 2),
], ids=["uneven", "single-group", "aligned", "one-then-many",
        "many-then-one", "empty-between", "alone"])
@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
def test_kernel_matches_per_group_product(sizes, M, N, layer, gated,
                                          small_blocks):
    G, K = len(sizes), 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(ks[0], (M, K))
    if layer is None:
        w = [jax.random.normal(k, (G, K, N)) for k in ks[1:]]
        mats = w
    else:
        stacks = [jax.random.normal(k, (3, G, K, N)) for k in ks[1:]]
        w = [a[layer] for a in stacks]
        mats = [GM.Slab(a, jnp.int32(layer)) for a in stacks]
    rhs = tuple(mats) if gated else mats[0]
    got = f32(GM.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                                interpret=True))
    assert got.shape == (M, N)
    start = 0
    for g, n in enumerate(sizes):
        rows = np.asarray(lhs)[start:start + n]
        want = rows @ np.asarray(w[0][g])
        if gated:
            want = np.asarray(jax.nn.silu(want)) * (rows @ np.asarray(w[1][g]))
        np.testing.assert_allclose(got[start:start + n], want, rtol=1e-4,
                                   atol=1e-4)
        start += n


def _copy_leads(sizes, bm, n_blocks):
    """The kernel's schedule stepped through by hand: the grid's visits in
    order, a weight copy started at each (group, N block) pair's first
    visit for the pair after it, and each copy's lead in visits."""
    steps = []
    for nb in range(n_blocks):
        start = 0
        for g, s in enumerate(sizes):
            if s:
                steps += [(g, nb)] * ((start + s - 1) // bm - start // bm + 1)
            start += s
    firsts = [i for i, p in enumerate(steps) if not i or steps[i - 1] != p]
    return [b - a for a, b in zip(firsts, firsts[1:])]


@pytest.mark.parametrize("sizes,bm,rows,early", [
    ((7, 50), 16, 5 * 16, 1 / 2),       # a shared block, visited twice
    ((128, 0, 384, 128), 128, 640, 1 / 3),
    ((512,) * 8, 128, 4096, 1.0),
    ((0, 300, 0), 128, 384, 1.0),       # one group, block after block
    ((0, 0), 128, 0, 0.0),
], ids=["shared", "padded", "balanced", "alone", "empty"])
def test_fetch_schedule_against_a_hand_count(sizes, bm, rows, early):
    assert GM.fetch_schedule(sizes, bm) == (rows, pytest.approx(early))
    # the second N block's copies: each one's lead is its predecessor's
    E = np.count_nonzero(sizes)
    leads = np.asarray(_copy_leads(sizes, bm, 2)[-E:] if E else [0])
    assert np.mean(leads >= 2) == pytest.approx(early)
    m = -(-max(sum(sizes), 1) // bm) * bm
    assert int(GM.visits(jnp.asarray(sizes, jnp.int32), m, bm).n) * bm \
        == rows


def test_padded_layout_shares_no_block():
    sizes = np.asarray((0, 1, 255, 256, 257))
    assert GM.padded(sizes, 256).tolist() == [0, 256, 256, 256, 512]
    rows, _ = GM.fetch_schedule(GM.padded(sizes, 256), 256)
    assert rows == int(GM.padded(sizes, 256).sum())


def _layer(E, dtype, seed=0, **over):
    cfg = get_config("mixtral-8x7b").reduced(num_experts=E, d_model=D,
                                              d_ff=F, **over)
    p = MOE.init_moe(cfg, jax.random.PRNGKey(seed), dtype)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, T, D)) \
        .astype(dtype)
    return cfg, p, x


def _reference(p, x, cfg):
    y = ref.moe_dropless_ref(x.reshape(-1, x.shape[-1]), p["router"],
                             p["w_gate"], p["w_up"], p["w_down"],
                             cfg.experts_per_token)
    return np.asarray(y).reshape(x.shape)


def _run(p, x, cfg, path, monkeypatch):
    monkeypatch.setattr(MOE, "expert_path", lambda *a: path)
    with MOE.collect_loads() as got:
        y, _ = MOE.moe_ffn(p, x, cfg)
    return f32(y), got


@pytest.mark.parametrize("E", [8, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dropless_layer_matches_reference(E, dtype, small_blocks,
                                          monkeypatch):
    cfg, p, x = _layer(E, dtype)
    got, loads = _run(p, x, cfg, "interpret", monkeypatch)
    want = _reference(p, x, cfg)
    top = np.abs(want).max()
    # bf16: the rows, the gated hidden state and the expert outputs are
    # rounded to bf16 (2^-8 relative) where the reference keeps f32
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(got - want).max() <= tol * top
    counts, = loads
    assert counts.shape == (E,) and int(counts.sum()) == 2 * T * 2


def test_capacity_path_at_capacity_T_equals_dropless(small_blocks,
                                                     monkeypatch):
    cfg, p, x = _layer(8, jnp.float32)
    assert MOE.capacity(T, cfg) == T
    kernel, k_loads = _run(p, x, cfg, "interpret", monkeypatch)
    padded, c_loads = _run(p, x, cfg, "ref", monkeypatch)
    np.testing.assert_allclose(kernel, padded, rtol=1e-5,
                               atol=1e-5 * np.abs(kernel).max())
    np.testing.assert_array_equal(k_loads[0], c_loads[0])


def test_dropless_where_capacity_would_drop(monkeypatch):
    """A router that sends most tokens to expert 0: at the former default
    capacity factor (1.25) the capacity dispatch drops assignments and
    misses the reference; the published (dropless) routing does not."""
    cfg, p, x = _layer(8, jnp.float32)
    x = x + 1.0  # every token leans towards expert 0
    p = dict(p, router=p["router"].at[:, 0].add(0.05))
    want = _reference(p, x, cfg)
    top = np.abs(want).max()
    dropping = dataclasses.replace(cfg, dropless=False, capacity_factor=1.25)
    cap = MOE.capacity(T, dropping)
    y, (loads,) = _run(p, x, dropping, "ref", monkeypatch)
    assert int(np.asarray(loads).max()) > 2 * cap  # in each of two groups
    assert np.abs(y - want).max() > 0.1 * top
    y, _ = _run(p, x, cfg, "ref", monkeypatch)
    assert np.abs(y - want).max() <= 1e-5 * top


def test_published_mixtral_routes_dropless():
    for cfg in (get_config("mixtral-8x7b"),
                get_config("mixtral-8x7b").reduced()):
        assert cfg.dropless
        assert all(MOE.capacity(t, cfg) == t for t in (1, 8, 2048))
    llama4 = get_config("llama4-scout-17b-a16e")
    assert not llama4.dropless and MOE.capacity(2048, llama4) < 2048


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch as a TPU backend would see it (nothing is run)."""
    monkeypatch.setattr(MOE.jax, "default_backend", lambda: "tpu")


def test_dispatch_cpu_takes_capacity_path():
    assert jax.default_backend() == "cpu"
    assert MOE.expert_path(get_config("mixtral-8x7b"), 2048) == "ref"


def test_dispatch_tpu(on_tpu):
    mixtral = get_config("mixtral-8x7b")
    assert MOE.expert_path(mixtral, 2048) == "pallas"
    assert MOE.expert_path(mixtral, 1) == "pallas"
    # a stated capacity that admits every token drops none either
    wide = dataclasses.replace(mixtral, dropless=False, capacity_factor=4.0)
    assert MOE.expert_path(wide, 2048) == "pallas"
    for arch in ("llama4-scout-17b-a16e", "jamba-1.5-large-398b"):
        assert MOE.expert_path(get_config(arch), 2048) == "ref", arch
    with activation_sharding({}):
        assert MOE.expert_path(mixtral, 2048) == "ref"


@pytest.mark.parametrize("where", ["cpu", "sharded"])
def test_off_kernel_programs_are_pallas_free(where, monkeypatch):
    cfg, p, x = _layer(8, jnp.bfloat16)
    if where == "sharded":
        monkeypatch.setattr(MOE.jax, "default_backend", lambda: "tpu")
    jaxpr = lambda: str(jax.make_jaxpr(
        lambda p, x: MOE.moe_ffn(p, x, cfg)[0])(p, x))
    if where == "sharded":
        with activation_sharding({}):
            text = jaxpr()
    else:
        text = jaxpr()
    assert "pallas_call" not in text and GM.NAME not in text


def test_path_counter_by_tokens(small_blocks, monkeypatch):
    cfg, p, x = _layer(4, jnp.float32)
    before = PATHS.copy()
    MOE.moe_ffn(p, x, cfg)
    monkeypatch.setattr(MOE, "expert_path", lambda *a: "interpret")
    jax.make_jaxpr(lambda p, x: MOE.moe_ffn(p, x, cfg))(p, x)
    got = {key: n for key, n in (PATHS - before).items()
           if key[0] == "experts"}
    assert got == {("experts", T, "ref"): 1, ("experts", T, "interpret"): 1}


@pytest.mark.parametrize("path", ["interpret", "ref"])
def test_reduced_mixtral_split_matches_unsplit(path, small_blocks,
                                               monkeypatch):
    """Segments chained without the wire give the unsplit model's logits,
    and each segment program returns its expert layers' loads."""
    monkeypatch.setattr(MOE, "expert_path", lambda *a: path)
    cfg = get_config("mixtral-8x7b").reduced(num_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    rt = CollabRuntime(cfg, params, cut_group=1)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                              cfg.vocab_size, jnp.int32)
    h, end_loads = rt._seg_fns[0](rt.p_end, toks)
    logits, cloud_loads = rt._seg_fns[-1](rt.p_cloud, h)
    want = rt.monolithic(params, toks)
    np.testing.assert_allclose(f32(logits), f32(want), rtol=1e-4,
                               atol=1e-4 * np.abs(f32(want)).max())
    assert end_loads.shape == (1, cfg.num_experts)
    assert cloud_loads.shape == (3, cfg.num_experts)
    assert (np.asarray(jnp.sum(jnp.concatenate([end_loads, cloud_loads]),
                               axis=1)) == 2 * 24 * 2).all()
    assert rt._expert_layers == [[0], [1, 2, 3]]


def test_gradient_is_the_reference_gradient(small_blocks):
    """The kernel's custom VJP (training through a dropless layer on a
    TPU) differentiates the reference, recomputed."""
    sizes = jnp.asarray((5, 0, 11), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lhs = jax.random.normal(ks[0], (16, 32))
    wg, wu = (jax.random.normal(k, (3, 32, 128)) for k in ks[1:])

    def loss(f):
        return lambda a, b, c: jnp.sum(f(a, b, c)[:16] ** 2)

    got = jax.grad(loss(lambda a, b, c: GM.grouped_matmul(
        a, (b, c), sizes, interpret=True)), argnums=(0, 1, 2))(lhs, wg, wu)
    want = jax.grad(loss(lambda a, b, c: ref.grouped_matmul_ref(
        a, (b, c), sizes, jax.nn.silu)), argnums=(0, 1, 2))(lhs, wg, wu)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
