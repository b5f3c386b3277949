#!/usr/bin/env python3
"""Bring-up smoke on one TPU: serve h2o-danube-3-4b at its published
widths through ``repro.launch.serve`` and check what comes out.

  python3 chip_smoke.py [--requests 16]

Phases, in one process (a child that imported JAX could not reach the
chip this one holds):

  1. ``serve()`` at full width with random bf16 weights from seed 0;
     the requests alternate 8- and 4-bit boundaries, each through the
     fused Pallas boundary pass and the Pallas dequantize.
  2. The fused boundary pass against its jnp reference at the serving
     shape: wire fields within one quantization level, equal ``best``,
     GAP feature / similarities / separability within the tolerances
     below.
  3. The wire round trip (Pallas quantize -> Pallas dequantize) within
     half a quantum per element, at 4 and 8 bits.
  4. Logits through the 8-bit wire against the same segments chained
     without it: relative error < 0.05 (the bound of tests/test_collab.py).

Earlier lines report the device, set-up and compile seconds, requests and
wall time per request, peak device memory, and the path (pallas / ref)
of every wire call.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU, or outside a checkout
of this repository, it exits non-zero before printing any result.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ARCH = "h2o-danube-3-4b"
WIRE_BITS = (8, 4)
# phase-2 tolerances: both sides run on the chip, the kernel in Mosaic and
# the reference through XLA, so they may round differently on ties
LEVELS = 1          # quantized values and zero-points, in levels
SCALE_RTOL = 1e-6
FEAT_RTOL = 1e-5    # of max |feat|
SIMS_ATOL = 1e-5    # similarities in [0, 1]; both dots at HIGHEST
SEP_RTOL = 1e-3     # separability divides by the top-2 gap
SPLIT_RTOL = 0.05   # phase 4, 8-bit wire


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def boundary_vs_ref(rt, centers, tokens, bits):
    import jax
    from repro.kernels import ops, ref

    h = rt._seg_fns[0](rt.p_segments[0], tokens)
    want = jax.jit(ref.fused_boundary_ref, static_argnums=2)(h, centers, bits)
    got = ops.boundary_pass(h, centers, bits)
    (p, s, z, f, sep, best, sims), (pr, sr, zr, fr, sepr, bestr, simsr) = \
        [[np.asarray(a) for a in out] for out in (got, want)]
    q, qr = ((ref.unpack4_ref(a) if bits == 4 else a).astype(np.int32)
             for a in (p, pr))
    dq = int(np.abs(q - qr).max())
    dz = float(np.abs(z - zr).max())
    ds = float(np.abs(s / sr - 1).max())
    df = float(np.abs(f - fr).max() / np.abs(fr).max())
    dsim = float(np.abs(sims - simsr).max())
    dsep = float(np.abs(sep - sepr).max() / max(np.abs(sepr).max(), 1e-6))
    log(f"boundary_vs_ref bits={bits} shape={tuple(h.shape)} "
        f"dtype={h.dtype} q_levels={dq} zp_levels={dz} scale_rel={ds:.3g} "
        f"feat_rel={df:.3g} sims_abs={dsim:.3g} sep_rel={dsep:.3g} "
        f"best={best.tolist()} best_ref={bestr.tolist()}")
    check(p.shape == pr.shape and p.dtype == np.uint8, "payload layout")
    check(dq <= LEVELS and dz <= LEVELS, "wire values off by > 1 level")
    check(ds <= SCALE_RTOL, "scale")
    check(df <= FEAT_RTOL, "GAP feature")
    check(dsim <= SIMS_ATOL, "similarities")
    check(dsep <= SEP_RTOL, "separability")
    check((best == bestr).all(), "best center")


def wire_roundtrip(rt, tokens, bits):
    import jax.numpy as jnp

    pkt, h = rt.segment_step(0, tokens, bits=bits)
    y = np.asarray(pkt.dequantize(jnp.float32))
    x = np.asarray(h.astype(jnp.float32))
    ratio = float((np.abs(y - x) / np.asarray(pkt.scale)).max())
    log(f"wire_roundtrip bits={bits} payload={tuple(pkt.payload.shape)} "
        f"wire_bytes={pkt.wire_bytes} max_err_in_quanta={ratio:.4f}")
    check(y.shape == x.shape, "round-trip shape")
    check(ratio <= 0.5 * (1 + 1e-3), "round trip beyond half a quantum")


def split_vs_chain(rt, tokens, bits):
    import jax.numpy as jnp

    pkt, h = rt.segment_step(0, tokens, bits=bits)
    out = np.asarray(rt.cloud_step(pkt).astype(jnp.float32))
    ref = np.asarray(rt._seg_fns[-1](rt.p_segments[-1], h)
                     .astype(jnp.float32))
    rel = float(np.abs(out - ref).max() / np.abs(ref).max())
    log(f"split_vs_chain bits={bits} logits={out.shape} rel_err={rel:.4g}")
    check(np.isfinite(out).all() and np.isfinite(ref).all(),
          "non-finite logits")
    return rel


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=16)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import serve

    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    log(f"compile_cache={enable_compile_cache()}")

    # ---- 1. the served path, full width
    served = serve(ARCH, requests=args.requests, wire_bits=WIRE_BITS)
    rt, stats = served.runtime, served.stats
    cfg = rt.cfg
    n_params = sum(int(a.size) for seg in rt.p_segments
                   for a in jax.tree.leaves(seg))
    log(f"model {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={n_params} "
        f"dtype={jax.tree.leaves(rt.p_segments[0])[0].dtype} "
        f"cut_group={rt.cut}/{cfg.num_groups}")
    log(f"init_s={served.init_s:.3f} compile_s={served.warmup_s:.3f} "
        f"requests={args.requests} wall_s={served.wall_s:.3f} "
        f"per_request_ms={served.wall_s / args.requests * 1e3:.3f}")
    check(len(stats.pipeline.tasks) == args.requests, "request count")
    paths = dict(sorted(ops.PATHS.items()))
    log("wire_calls " + " ".join(f"{op}/{b}b/{p}={n}"
                                 for (op, b, p), n in paths.items()))
    for bits in WIRE_BITS:
        for op in ("boundary", "dequantize"):
            check(paths.get((op, bits, "pallas"), 0) >= 1,
                  f"no {bits}-bit {op} call took the Pallas path")
    n_ref = sum(n for (_, _, p), n in paths.items() if p == "ref")
    log(f"wire_calls_ref={n_ref}")
    check(all(p == "pallas" for _, _, p in paths),
          "a served wire call did not run a compiled Pallas kernel")

    # ---- 2-4. outputs against the references
    key = jax.random.PRNGKey(1)
    one = jax.random.randint(key, (1, 8), 0, cfg.vocab_size, jnp.int32)
    batch = jax.random.randint(key, (4, 32), 0, cfg.vocab_size, jnp.int32)
    centers = jnp.asarray(served.engine.sched.probe_centers()[0],
                          jnp.float32)
    for bits in WIRE_BITS:
        boundary_vs_ref(rt, centers, one, bits)
        wire_roundtrip(rt, batch, bits)
    rel8 = split_vs_chain(rt, batch, 8)
    check(rel8 < SPLIT_RTOL, f"8-bit split logits rel_err {rel8} >= 0.05")
    split_vs_chain(rt, batch, 4)  # reported, not bounded

    peak = dev.memory_stats()["peak_bytes_in_use"]
    log(f"peak_bytes_in_use={peak} ({peak / 2**30:.3f} GiB)")
    check(peak < 16 * 2**30, "peak device memory over 16 GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
