#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_check.py [--workload NAME ...]

For each cell of ``BENCHMARK.json`` (or those named) it compiles, at the
cell's shapes and for one chip of a described ``v5e:2x2``: the program
that makes the weights from the seed and splits them, the end segment,
the fused boundary pass, the wire dequantize and the cloud segment.  It
prints each program's ``memory_analysis()`` (argument, output and
temporary bytes) and the weights' bytes, so a program that would not fit
the chip's memory shows here before any chip time is spent.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")}


def check_cell(spec, name: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    import weights as W
    from drivers.collab_sync import CALIB_TOKENS
    from repro.core.collab import CollabRuntime, split_params_multi
    from repro.core.costs import (A6000_SERVER, JETSON_NX, WIFI_5GHZ,
                                  transformer_graph)
    from repro.core.partitioner import coach_offline
    from repro.kernels.boundary import fused_boundary
    from repro.kernels.uaq import uaq_dequantize

    cell = spec.workload(name)
    cfg, pcfg, mix = spec.config(cell), spec.program_config(cell), spec.mix(cell)
    S, bits = int(mix["seq_len"]), int(mix["wire_bits"])
    off = coach_offline(transformer_graph(pcfg, batch=1, seq=S), JETSON_NX,
                        A6000_SERVER, WIFI_5GHZ(float(mix["link_mbps"])))
    n_end = sum(1 for i in off.decision.end_set if 0 < i <= pcfg.num_layers)
    cut = min(max(1, round(n_end / pcfg.group_size)), pcfg.num_groups - 1)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    out = {"cut_group": cut, "param_bytes": W.param_bytes(cfg)}
    make = jax.jit(lambda k: W.make_segments(
        k, cfg, lambda t: split_params_multi(t, pcfg, (cut,))))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    out["init"] = _mem(make.lower(key).compile())

    segs = jax.eval_shape(make, key)
    segs = jax.tree.map(sds, segs)
    rt = CollabRuntime(pcfg, [jax.tree.map(lambda _: None, s) for s in segs],
                       cut)
    toks = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one_chip)
    first = jax.jit(rt._first_forward)
    out["end_segment"] = _mem(first.lower(segs[0], toks).compile())
    rows = max(1, CALIB_TOKENS // S)
    calib = jax.ShapeDtypeStruct((rows, S), jnp.int32, sharding=one_chip)
    out["calibration_chunk"] = _mem(first.lower(segs[0], calib).compile())
    h = jax.ShapeDtypeStruct((1, S, pcfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    cen = jax.ShapeDtypeStruct((int(mix["n_labels"]), pcfg.d_model),
                               jnp.float32, sharding=one_chip)
    bnd = jax.jit(lambda x, c: fused_boundary(x, c, bits, interpret=False))
    out["boundary"] = _mem(bnd.lower(h, cen).compile())
    P = pcfg.d_model if bits == 8 else (pcfg.d_model + 1) // 2
    pay = jax.ShapeDtypeStruct((S, P), jnp.uint8, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((S, 1), jnp.float32, sharding=one_chip)
    deq = jax.jit(lambda p, s, z: uaq_dequantize(
        p, s, z, bits, jnp.bfloat16, interpret=False, n=pcfg.d_model))
    out["dequantize"] = _mem(deq.lower(pay, sc, sc).compile())
    last = jax.jit(rt._last_forward)
    out["cloud_segment"] = _mem(last.lower(segs[1], h).compile())
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spec as SP

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = SP.Spec.load(ROOT)
    for name in args.workload or [w["name"] for w in spec.bench["workloads"]]:
        print(json.dumps({"workload": name,
                          **check_cell(spec, name, one_chip)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
