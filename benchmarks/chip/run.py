#!/usr/bin/env python3
"""COACH on the chip: one cell of ``BENCHMARK.json``, measured from the
client's side.

  python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up (timed as ``setup_s``): imports, the cell's request path
(``drivers/<driver>.py``: weights from the seed, calibration, engine) and
a warm-up of every shape the window uses.  Then the window: for
``--seconds`` seconds the traffic mix (``traffic/<mix>.json``) offers
requests, open loop on a Poisson schedule or closed loop from one client,
and the host clock times each from its due time (open) or its send
(closed) to its result on the host.  With ``--trace 0`` the result holds
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and the result holds the per-layer metrics
(``metrics/<name>.py``) and a breakdown.  After the window the program's
state is freed and a seeded sample of the finished requests is checked
against the float32 reference (``check.py``); ``correct`` says whether
every number compared lies within its limit.

Earlier lines of standard output describe the run; the last is one JSON
object.  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NO_CHIP = 3


def log(msg: str) -> None:
    print(msg, flush=True)


class Recorder:
    """Per-request host-clock records, and the same spans as
    ``TraceAnnotation``s when the window is traced."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.requests: list = []
        self.cur: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.cur[name] = (t0, time.perf_counter())

    def request(self, driver, task, due: float) -> None:
        self.cur = {"id": task.id, "due": due, "start": time.perf_counter()}
        driver.serve(task)
        self.cur["end"] = time.perf_counter()
        self.requests.append(self.cur)


def wait_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left - 0.002 if left > 0.003 else 0)


def closed_loop(driver, rec: Recorder, tasks: list, seconds: float):
    """One client: the next request goes when the last one returns.
    ``tasks`` is made before the window for ``max_rps``; should the
    client outrun that, more are appended."""
    t0, i = time.perf_counter(), 0
    while time.perf_counter() - t0 < seconds:
        if i == len(tasks):
            tasks += driver.tasks(len(tasks))
        rec.request(driver, tasks[i], time.perf_counter())
        i += 1
    return t0, time.perf_counter()


def open_loop(driver, rec: Recorder, tasks, offsets):
    t0 = time.perf_counter() + 0.01
    late = []
    for task, off in zip(tasks, offsets):
        due = t0 + off
        idle = time.perf_counter() < due
        wait_until(due)
        if idle:  # the server was waiting: any delay is the generator's
            late.append(time.perf_counter() - due)
        rec.request(driver, task, due)
    return t0, time.perf_counter(), late


class Compiles:
    """Counts backend compilations while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.active = False
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name == self.EVENT:
            self.n += 1


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


class Context:
    """What a per-layer metric reader gets: the run's records and, in a
    traced run, the reduced device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None, require_chip: bool = True, spec_root: Path = ROOT,
         driver_hook=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import spec as SP
    spec = SP.Spec.load(spec_root)
    cell = spec.workload(args.workload)

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < int(cell["chips"])):
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    if devices[0].platform == "tpu":
        # the persistent compile cache lives at a fixed path in the
        # checkout, whatever the environment names, so that only a cell's
        # first run compiles and no two checkouts share entries
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # no eviction: an evicting cache lost its entries on the chip
        jax.config.update("jax_compilation_cache_max_size", -1)

    import numpy as np

    import check as CK
    import reference as R
    import flops as FL
    import peaks as PK
    import tracing as TR
    from traffic.generator import arrival_offsets

    dev = devices[0]
    peak = PK.peaks(dev.device_kind) if require_chip else \
        PK.PEAKS["TPU v5 lite"]
    cfg, mix = spec.config(cell), spec.mix(cell)
    limits = spec.limits(cell)
    run = Context(cfg=cfg, pcfg=spec.program_config(cell), mix=mix,
                  seed=args.seed)
    rec = Recorder(bool(args.trace))
    run.span = rec.span
    if driver_hook is not None:
        driver_hook(run)
        cfg, mix = run.cfg, run.mix
    drivers = SP.load_module(HERE / "drivers" / f"{mix['driver']}.py",
                             f"driver_{mix['driver']}")
    from repro.kernels import ops

    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    t = time.perf_counter()
    log(f"imports_s={t - T_START:.3f}")
    driver = drivers.Driver(run)
    log("cell " + json.dumps({"workload": cell["name"], **driver.describe(),
                              "loop": mix["loop"]}))
    log(f"driver_s={time.perf_counter() - t:.3f}")

    # ---- the window's tasks and schedule, made before it opens
    if mix["loop"] == "open":
        offsets = arrival_offsets(float(mix["rate_rps"]), args.seconds,
                                  args.seed)
        tasks = driver.tasks(len(offsets))
    else:
        tasks = driver.tasks(int(np.ceil(args.seconds * mix["max_rps"])))
    t = time.perf_counter()
    driver.warm_up()
    log(f"warm_up_s={time.perf_counter() - t:.3f}")
    # what set-up made lives as long as the process: a full collection
    # walking it would stall a request by some 70-100 ms
    gc.collect()
    gc.freeze()
    compiles = Compiles()
    trace_dir = tempfile.mkdtemp(prefix="coach-trace-") if args.trace \
        else None
    paths0 = ops.PATHS.copy()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.6f}")

    # ---- the window
    compiles.active = True
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window") if trace_dir \
            else contextlib.nullcontext():
        if mix["loop"] == "open":
            t0, t1, late = open_loop(driver, rec, tasks, offsets)
        else:
            t0, t1 = closed_loop(driver, rec, tasks, args.seconds)
            late = []
    if trace_dir:
        jax.profiler.stop_trace()
    compiles.active = False
    reqs = rec.requests
    sent = ops.PATHS - paths0
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    log(f"window_s={t1 - t0:.6f} requests={len(reqs)} "
        f"compiles_in_window={compiles.n} "
        f"generator_late_ms_max={max(late, default=0.0) * 1e3:.3f}")
    log("wire_calls " + " ".join(f"{op}/{b}b/{path}={n}"
                                 for (op, b, path), n in sorted(sent.items())))
    log(f"memory_peak_bytes={peak_bytes}")

    lat_ms = [(r["end"] - (r["due"] if mix["loop"] == "open"
                           else r["start"])) * 1e3 for r in reqs]
    p95 = percentile(lat_ms, 95)
    log(f"latency requests={len(lat_ms)} beyond_p95="
        f"{sum(x > p95 for x in lat_ms)} p50_ms={percentile(lat_ms, 50):.4f}"
        f" p95_ms={p95:.4f} max_ms={max(lat_ms):.4f}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if not args.trace:
        e2e = {"latency_p50_ms": percentile(lat_ms, 50),
               "latency_p95_ms": p95,
               "throughput_rps": len(reqs) / (t1 - t0),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics(cell, "end_to_end")}
    else:
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        t = time.perf_counter()
        view = TR.load(files[0], ("window", "sched", "classify"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(requests=reqs, loop=mix["loop"], cfg=cfg, mix=mix,
                      peak=peak, flops=FL, trace=view,
                      served=driver.served, shapes=driver.describe())
        metrics = {}
        for m in spec.metrics(cell, "per_layer"):
            mod = SP.load_module(HERE / "metrics" / f"{m['name']}.py",
                                 "metric_" + m["name"].replace(".", "_"))
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        w0, w1 = view.window
        busy = view.busy_in(w0, w1)
        device.update(busy_s=busy * 1e-9, window_s=(w1 - w0) * 1e-9)
        idle = sorted(((view.label_at((s + e) / 2, ("classify", "sched"))
                        or ("wait_arrival" if mix["loop"] == "open"
                            else "between_requests"), (e - s) * 1e-9)
                       for s, e in TR.gaps(view.busy, w0, w1)),
                      key=lambda g: -g[1])
        labels = {"classify": "forward_host", "sched": "sched"}
        breakdown = {"device_ops": [[k, v] for k, v in view.top_ops(10)],
                     "idle_gaps": [[labels.get(k, k), v]
                                   for k, v in idle[:10]]}
        log(f"trace_read_s={time.perf_counter() - t:.3f} ops={len(view.ops)}"
            f" programs={len(view.modules)}")
        for name, secs in view.top_ops(10):
            log(f"device_op {name} {secs:.6f}s")

    # ---- the output check, once the program's device state is freed
    served = driver.served
    failed = sum(not np.isfinite(served[r["id"]]["logits"]).all()
                 for r in reqs)
    ids = CK.sample_ids([r["id"] for r in reqs], int(mix["check_requests"]),
                        args.seed)
    by_id = {t.id: t for t in tasks}
    prompts = np.concatenate([driver._tokens(by_id[i]) for i in ids])
    cut = driver.cut
    picked = [{"logits": served[i]["logits"], "feat": served[i]["feat"],
               "centers": served[i]["centers"],
               "best_index": int(np.searchsorted(served[i]["valid"],
                                                 served[i]["best"]))}
              for i in ids]
    driver.close()
    del driver, served
    gc.collect()
    t = time.perf_counter()
    from weights import seed_key
    ref = R.forward(cfg, seed_key(args.seed), prompts, cut,
                    block=int(mix["check_block"]))
    values = CK.readings(picked, ref.logits, ref.feat)
    if ref.loads.size:
        from repro.models.moe import capacity
        cap = capacity(int(mix["seq_len"]), run.pcfg)
        log(f"expert_load max={int(ref.loads.max())} capacity={cap} "
            f"over_capacity={CK.over_capacity(ref.loads, cap)} of "
            f"{int(ref.loads.sum())} assignments in {len(ids)} prompts")
        # the served logits' distance beside the last token's router margin
        errs = CK.per_request(picked, ref.logits, ref.feat)["logit_err"]
        log("routing_margin_vs_logit_err " + " ".join(
            f"{m:.2e}:{e:.4f}" for e, m in sorted(zip(errs, ref.margin),
                                                  reverse=True)))
    correct, rows = CK.judge(values, limits)
    correct = correct and failed == 0
    log(f"check_s={time.perf_counter() - t:.3f} sampled={len(ids)}")
    for name, v, lim in rows:
        print(f"check {name}={v:.6g} limit={lim:.6g}", file=sys.stderr)
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in rows}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
