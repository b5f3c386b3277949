"""The weights, the reference and the control at a small size on the CPU:
the benchmark's weight layout is the program's, the float32 reference
agrees with the program's own float32 forward pass, and the control
(float8 inputs to every weight product) fails the cell's limits."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check as CK
import control
import reference as R
import small
import spec as SP
import weights as W
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPEC = SP.Spec(ROOT, BENCH)
CELLS = {w["config"]: w for w in BENCH["workloads"]}
KEY = W.seed_key(2 ** 40 + 3)


def small_cfg(config, dtype="float32"):
    cell = CELLS[config]
    cfg, pcfg = small.shrink(SPEC.config(cell), SPEC.program_config(cell))
    return dict(cfg, dtype=dtype), small.dropless(pcfg)


@pytest.mark.parametrize("config", sorted(CELLS))
def test_layout_is_the_programs(config):
    from repro.models import model as M

    cell = CELLS[config]
    cfg, pcfg = SPEC.config(cell), SPEC.program_config(cell)
    want = jax.eval_shape(lambda k: M.init_params(pcfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: W.make_segments(k, cfg, lambda t: [t])[0],
                         KEY)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, want, got)))


def test_segments_equal_layers():
    cfg, pcfg = small_cfg("danube3-4b", "bfloat16")
    from repro.core.collab import split_params_multi

    end, cloud = jax.jit(lambda k: W.make_segments(
        k, cfg, lambda t: split_params_multi(t, pcfg, (1,))))(KEY)
    last = W.layer_params(KEY, cfg, cfg["num_hidden_layers"] - 1)
    for name, x in last.items():
        a, b = name.split("/")
        assert (cloud["groups"][0][a][b][-1] == x).all(), name
    assert (end["embed"] == W.outer_params(KEY, cfg)["embed"]).all()


@pytest.mark.parametrize("config", sorted(CELLS))
def test_reference_matches_program_in_float32(config):
    from repro.models import model as M

    cfg, pcfg = small_cfg(config)
    params = jax.jit(lambda k: W.make_segments(k, cfg, lambda t: [t])[0])(KEY)
    toks = np.random.default_rng(0).integers(
        0, pcfg.vocab_size, (3, 96)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        h, _, _ = M.forward(params, pcfg, jnp.asarray(toks))
        want = np.asarray(M._lm_head(params, pcfg, h[:, -1]))
        h1 = M._embed(params, pcfg, jnp.asarray(toks))
        for i in range(1):
            h1, _, _ = M._block_full(
                jax.tree.map(lambda x: x[i], params["groups"][0]), h1, pcfg,
                pcfg.pattern[0], jnp.broadcast_to(jnp.arange(96), (3, 96)),
                False, 96)
    logits, feat, _, _ = R.forward(cfg, KEY, toks, cut=1)
    assert np.abs(logits - want).max() <= 1e-5 * np.abs(want).max()
    np.testing.assert_allclose(feat, np.asarray(h1).mean(axis=1),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("config", sorted(CELLS))
def test_control_fails_the_limits(config):
    cfg, _ = small_cfg(config, "bfloat16")
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg["vocab_size"], (4, 64)).astype(np.int32)
    ref_l, ref_f, _, _ = R.forward(cfg, KEY, toks, cut=1)
    low_l, low_f, _, _ = R.forward(cfg, KEY, toks, cut=1, lower=True)
    centers = [rng.normal(size=(16, cfg["hidden_size"])) for _ in toks]
    served = control.control_served([{"centers": c} for c in centers],
                                    low_f, low_l)
    values = CK.readings(served, ref_l, ref_f)
    correct, _ = CK.judge(values, SPEC.limits(CELLS[config]))
    assert correct is False, values
