"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at the width of h2o-danube-3-4b (D = 3840), without a chip; the
flash-attention kernel at danube's and Mixtral's heads, the cloud
segment's program with its attention on that kernel, and a Mixtral stage's
cloud segment with its experts on the grouped-matmul kernel, and that
kernel alone at Mixtral's expert shapes.

The TPU compiler refuses what interpret mode accepts: lane slices it
cannot lower, tiles that overflow VMEM, grids that do not divide the
array.  Each case lowers one kernel with ``interpret=False`` on
``ShapeDtypeStruct``s placed on a described (not attached) v5e chip and
compiles it; a compile that fails here would fail on the chip.

The topology is described inside a module fixture (never at import):
describing it loads the TPU library, which one process at a time may
hold, so only the worker that runs this file does so.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.collab import CollabRuntime, split_params_multi
from repro.kernels.attention import flash_attention
from repro.kernels import grouped_matmul as GM
from repro.kernels.boundary import fused_boundary
from repro.kernels.semantic_cache import semantic_probe
from repro.kernels.uaq import uaq_dequantize, uaq_quantize
from repro.models import layers, moe
from repro.models import model as M

D = 3840          # h2o-danube-3-4b d_model
L = 16            # semantic-cache labels served by repro.launch.serve
SHAPES = [(1, 8, D), (8, 512, D)]   # one request; a full prefill batch
ROWS = [8, 1024, 1000]              # 1000: not a multiple of the row block
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("bits", [4, 8])
def test_fused_boundary_compiles_for_v5e(one_chip, bits, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    c = jax.ShapeDtypeStruct((L, D), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: fused_boundary(a, b, bits, interpret=False), x, c)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_semantic_probe_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    c = jax.ShapeDtypeStruct((L, D), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: semantic_probe(a, b, interpret=False), x, c)


@pytest.mark.parametrize("M", ROWS)
@pytest.mark.parametrize("bits", [4, 8])
def test_uaq_quantize_compiles_for_v5e(one_chip, bits, M):
    x = jax.ShapeDtypeStruct((M, D), jnp.bfloat16, sharding=one_chip)
    _compile(lambda a: uaq_quantize(a, bits, interpret=False), x)


@pytest.mark.parametrize("M", ROWS)
@pytest.mark.parametrize("bits", [4, 8])
def test_uaq_dequantize_compiles_for_v5e(one_chip, bits, M):
    p = jax.ShapeDtypeStruct((M, D * bits // 8), jnp.uint8,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((M, 1), jnp.float32, sharding=one_chip)
    _compile(lambda a, b, c: uaq_dequantize(a, b, c, bits, jnp.bfloat16,
                                            n=D, interpret=False), p, s, s)


# the benchmark's prompts, and serve()'s 8-token ones (a single block)
@pytest.mark.parametrize("arch,S", [("h2o-danube-3-4b", 2048),
                                    ("mixtral-8x7b", 2048),
                                    ("h2o-danube-3-4b", 8)])
def test_flash_attention_compiles_for_v5e(one_chip, arch, S):
    cfg = get_config(arch)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.ShapeDtypeStruct((1, S, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, KV, hd), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda a, b, c: flash_attention(
        a, b, c, 1.0 / math.sqrt(hd), cfg.sliding_window, interpret=False),
        q, kv, kv)


def test_cloud_segment_takes_flash_kernel_for_v5e(one_chip, monkeypatch):
    """``_last_forward`` at the benchmark's (1, 2048, 3840), with the
    dispatch a TPU process makes: the kernel is in the program and no
    query chunk's bf16[8, 2048, 1024, 4] scores remain."""
    cfg = get_config("h2o-danube-3-4b")
    segs = jax.eval_shape(lambda: split_params_multi(
        M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16), cfg, (3,)))
    segs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), segs)
    rt = CollabRuntime(cfg, list(segs), 3)
    h = jax.ShapeDtypeStruct((1, 2048, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    monkeypatch.setattr(layers, "attention_path", lambda *a: "pallas")
    text = _compile(rt._last_forward, segs[-1], h).as_text()
    assert "coach_flash_attention" in text
    assert "bf16[8,2048,1024,4]" not in text


# Mixtral-8x7B's expert matrices: gate and up as a pair, then down
@pytest.mark.parametrize("K,N,pair", [(4096, 14336, 2), (14336, 4096, 1)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_compiles_for_v5e(one_chip, K, N, pair):
    """The expert kernel as the dropless layer calls it at 2048 tokens:
    4096 routed rows and room for padding at ``BLOCK_M``, each matrix a
    slab of a 4-layer stack; two weight slots per matrix and the row
    blocks fit VMEM."""
    E, bm = 8, GM.BLOCK_M
    m = -(-(4096 + E * (bm - 1)) // bm) * bm

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(lhs, sizes, i, *stacks):
        slabs = tuple(GM.Slab(w, i) for w in stacks)
        return GM.grouped_matmul(lhs, slabs if pair == 2 else slabs[0],
                                 sizes, interpret=False)

    _compile(layer, spec((m, K)), spec((E,), jnp.int32),
             spec((), jnp.int32), *[spec((4, E, K, N))] * pair)


def test_mixtral_stage_takes_grouped_matmul_for_v5e(one_chip, monkeypatch):
    """``_last_forward`` of the benchmark's 4-layer Mixtral stage at
    (1, 2048, 4096), with the dispatch a TPU process makes: every expert
    layer runs the grouped-matmul kernel, and the capacity dispatch's
    padded (1, 8, 2049, 14336) expert buffer is gone, and no layer's
    (8, K, N) expert matrices are sliced out of the stack."""
    cfg = dataclasses.replace(get_config("mixtral-8x7b"), num_layers=4)
    segs = jax.eval_shape(lambda: split_params_multi(
        M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16), cfg, (1,)))
    segs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), segs)
    rt = CollabRuntime(cfg, list(segs), 1)
    h = jax.ShapeDtypeStruct((1, 2048, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    monkeypatch.setattr(layers, "attention_path", lambda *a: "pallas")
    monkeypatch.setattr(moe, "expert_path", lambda *a: "pallas")
    compiled = _compile(rt._last_forward, segs[-1], h)
    text = compiled.as_text()
    assert "coach_grouped_matmul" in text
    assert "bf16[1,8,2049,14336]" not in text
    # the cloud's 3 layers of expert matrices are read in place from their
    # stacks: no layer's 0.94 GB matrices are sliced out and copied
    experts = set(re.findall(
        r"bf16\[(?:\d+,)*8,(?:4096,14336|14336,4096)\]", text))
    assert experts == {"bf16[3,8,4096,14336]", "bf16[3,8,14336,4096]"}
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
