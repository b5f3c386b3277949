"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at the width of h2o-danube-3-4b (D = 3840), without a chip.

The TPU compiler refuses what interpret mode accepts: lane slices it
cannot lower, tiles that overflow VMEM, grids that do not divide the
array.  Each case lowers one kernel with ``interpret=False`` on
``ShapeDtypeStruct``s placed on a described (not attached) v5e chip and
compiles it; a compile that fails here would fail on the chip.

The topology is described inside a module fixture (never at import):
describing it loads the TPU library, which one process at a time may
hold, so only the worker that runs this file does so.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.boundary import fused_boundary
from repro.kernels.semantic_cache import semantic_probe
from repro.kernels.uaq import uaq_dequantize, uaq_quantize

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
