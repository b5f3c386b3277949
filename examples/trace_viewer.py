"""Bubble-attribution walkthrough: trace a ResNet101 3-tier run, print
the per-cause idle table, and export a Perfetto/Chrome trace.

ResNet101 is partitioned by the real offline planner onto the 3-tier
deployment (Jetson-NX + AGX-Orin + A6000 — the same device/link table
the ``multihop`` bench uses), a steady stream with the hop-level
semantic-exit cascade runs through the event simulator
(``core.sim.simulate_stream``, every tier a pool of one replica) with a
live ``TraceRecorder``, and the observability layer (``repro.obs``) answers
the question ``bubble_fraction`` can't: not *how much* each resource
idled, but *why* — warmup, drain, upstream starvation, batch formation,
exit releases, and the rest of the closed cause enum, with the
conservation identity ``busy + sum(bubbles) = horizon`` checked per
resource.

The exported JSON opens in https://ui.perfetto.dev (or
``chrome://tracing``): one track per resource, busy spans on the main
row, waits and attributed bubbles on child rows.

  PYTHONPATH=src python examples/trace_viewer.py \
      [--tasks 160] [--out experiments/trace/resnet101_3tier.json]
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.multihop import DEPLOYMENTS, decide_exit_hops
from repro.core.partitioner import coach_offline_multihop
from repro.core.pipeline import plan_from_stage_times, run_pipeline
from repro.models.cnn import resnet101
from repro.obs.bubbles import attribute, chain_resources
from repro.obs.export import text_summary, write_chrome_trace
from repro.obs.trace import TraceRecorder, assert_traces_match
from repro.serving.async_engine import run_pipeline_async

N_TIERS = 3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=160)
    ap.add_argument("--out",
                    default="experiments/trace/resnet101_3tier.json")
    args = ap.parse_args()

    devices, links = DEPLOYMENTS[N_TIERS]
    off = coach_offline_multihop(resnet101(), devices, links)
    st = off.times
    period = st.max_stage * 1.05
    exit_hops = decide_exit_hops(N_TIERS - 1, args.tasks)
    plans = [plan_from_stage_times(st, exit_hop=eh) for eh in exit_hops]

    rec = TraceRecorder()
    pr = run_pipeline(plans, arrival_period=period, links=list(links),
                      sink=rec)
    # the differential pin extends to span timelines: the executor's
    # trace of the same stream is the same trace
    rec_a = TraceRecorder()
    run_pipeline_async(plans, arrival_period=period, links=list(links),
                       sink=rec_a)
    assert_traces_match(rec, rec_a, tol=1e-6)

    att = attribute(rec, resources=chain_resources(
        pr.n_hops, pr.pool_sizes))
    print(f"model=resnet101 tiers={N_TIERS} tasks={args.tasks} "
          f"exit_ratio={pr.exit_ratio:.2%} makespan={pr.makespan:.3f}s "
          f"spans={len(rec)} (sim == async at 1e-6)")
    print()
    print(text_summary(att))

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    write_chrome_trace(out, rec, att)
    print()
    print(f"wrote {out} — open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
