#!/usr/bin/env python3
"""Readings that the output check's limits are set from, for one cell.

  python3 benchmarks/chip/control.py --workload NAME --seeds 11,12,13 [--seconds 5]

For each seed, in this one process (it holds the chip): the cell's
program serves the cell's traffic for a short window, exactly as a run
of ``run.py`` does, and the same seeded sample of finished requests is
compared with the float32 reference (``check.readings``): the program's
readings, the lower ends of the limits.  Then two controls are read on
the same prompts:

- ``fp8``: the reference itself computed with float8 (e4m3) inputs to
  every weight product, the precision below the configuration's bfloat16,
  put in the program's place (its logits and its GAP feature, probed
  against the centers each request met);
- ``wire4``: the program with its 4-bit wire, the precision below the
  traffic's 8-bit one, a path the program has of its own.

Each line printed is one JSON object: ``{"seed", "kind", <numbers>}``.
Off a TPU it exits with code 3, like ``run.py``; the CPU test drives
``read_seed`` directly at a small size.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def control_served(picked, feats, logits):
    """The control's answers in the shape ``check.readings`` takes: its
    logits, its feature, and the center its feature would pick among the
    centers each request was probed with."""
    import check as CK
    import numpy as np

    out = []
    for s, f, lg in zip(picked, feats, logits):
        out.append({"logits": lg, "feat": f, "centers": s["centers"],
                    "best_index": int(np.argmax(CK.sims(
                        np.asarray(f, np.float64),
                        np.asarray(s["centers"], np.float64))))})
    return out


def serve_sample(driver, mix, seconds: float, seed: int):
    """Serve the cell's traffic for ``seconds`` (closed loop at the cell's
    prompt length; an open mix's arrivals change no answer), then pick
    the check's seeded sample of the finished requests."""
    import check as CK
    import numpy as np

    driver.warm_up()
    tasks = driver.tasks(int(np.ceil(seconds * mix.get("max_rps", 100))))
    done, t0 = [], time.perf_counter()
    for task in tasks:
        if time.perf_counter() - t0 >= seconds and \
                len(done) >= int(mix["check_requests"]):
            break
        driver.serve(task)
        done.append(task)
    ids = CK.sample_ids([t.id for t in done], int(mix["check_requests"]),
                        seed)
    by_id = {t.id: t for t in done}
    prompts = np.concatenate([driver._tokens(by_id[i]) for i in ids])
    picked = [{"logits": driver.served[i]["logits"],
               "feat": driver.served[i]["feat"],
               "centers": driver.served[i]["centers"],
               "best_index": int(np.searchsorted(driver.served[i]["valid"],
                                                 driver.served[i]["best"]))}
              for i in ids]
    return prompts, picked


def read_seed(run, driver_cls, seed: int, seconds: float) -> list:
    """[(kind, readings)] for one seed: program, wire4, fp8."""
    import jax.numpy as jnp
    import numpy as np

    import check as CK
    import reference as R
    from weights import seed_key

    run.seed = seed
    mix = run.mix
    driver = driver_cls(run)
    prompts, picked = serve_sample(driver, mix, seconds, seed)
    cut = driver.cut
    # the same prompts through the program's own 4-bit wire, each probed
    # against the centers its request met
    driver.bits = 4
    picked4 = []
    for p, s in zip(prompts, picked):
        valid = np.arange(len(s["centers"]))
        feat, row, pr = driver._forward(jnp.asarray(p[None]), s["centers"],
                                        valid)
        picked4.append({"logits": row, "feat": feat,
                        "centers": s["centers"], "best_index": int(pr.best)})
    driver.close()
    del driver
    gc.collect()
    key = seed_key(seed)
    block = int(mix["check_block"])
    ref = R.forward(run.cfg, key, prompts, cut, block=block)
    low = R.forward(run.cfg, key, prompts, cut, lower=True, block=block)
    return [("program", CK.readings(picked, ref.logits, ref.feat)),
            ("wire4", CK.readings(picked4, ref.logits, ref.feat)),
            ("fp8", CK.readings(control_served(picked, low.feat, low.logits),
                                ref.logits, ref.feat))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    import spec as SP
    from run import NO_CHIP, Context

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return NO_CHIP
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    spec = SP.Spec.load(ROOT)
    cell = spec.workload(args.workload)
    mix = spec.mix(cell)
    drivers = SP.load_module(HERE / "drivers" / f"{mix['driver']}.py",
                             "driver")
    run = Context(cfg=spec.config(cell), pcfg=spec.program_config(cell),
                  mix=mix, span=lambda name: contextlib.nullcontext())
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, vals in read_seed(run, drivers.Driver, seed, args.seconds):
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "kind": kind, **(vals or {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
