"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--only table1,fig5] [--out experiments/bench]

Prints every module's CSV and writes it under --out.
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import (ablation, arch_partition, batching, bubbles,
                        calibration, fig1_locality, fig2_schemes,
                        fig5_dynamic, fig6_fig7_bandwidth, kernels_bench,
                        multihop, multitenant, planner, resilience,
                        roofline, routing, table1_latency, table2_context)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

MODULES = {
    "fig1": fig1_locality,
    "fig2": fig2_schemes,
    "table1": table1_latency,
    "table2": table2_context,
    "fig5": fig5_dynamic,
    "fig67": fig6_fig7_bandwidth,
    "ablation": ablation,
    "arch_partition": arch_partition,
    "kernels": kernels_bench,    # us/call of the shared ops entry points
    "calibration": calibration,  # measured-vs-modeled stage times, gated
    # multihop + multitenant + planner merge their rows into one
    # canonical BENCH_pipeline.json via benchmarks.bench_io
    "multihop": multihop,        # 2-hop vs 3-hop paired sim/async rows
    "multitenant": multitenant,  # per-tenant fairness-vs-bubble rows
    "planner": planner,          # offline-search candidate throughput
    "batching": batching,        # micro-batched vs unbatched paired rows
    "routing": routing,          # replicated-tier throughput-vs-m sweeps
    "bubbles": bubbles,          # per-cause idle attribution, pinned+gated
    "resilience": resilience,    # churn/degrade storylines, replan gated
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(MODULES)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for name in names:
        t0 = time.time()
        rows = MODULES[name].run(out_dir=str(out))
        dt = time.time() - t0
        text = "\n".join(rows)
        print(text)
        print(f"# {name}: {dt:.1f}s")
        (out / f"{name}.csv").write_text(text + "\n")


if __name__ == "__main__":
    main()
