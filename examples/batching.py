"""Continuous micro-batching walkthrough: one overloaded stream, two runs.

ResNet101 is cut by the offline planner onto the 2-tier (Jetson-NX +
A6000; pass ``--tiers 3`` for the +AGX-Orin chain) deployment over
10 GbE, each segment's service time is split into its per-launch fixed
cost and per-sample marginal (``core.costs.segment_batch_split``), and
the auto batch-size finder (``serving.batching.auto_batch_caps``)
converts a staleness slack budget into per-tier batch caps.  The same
overloaded arrival stream then runs twice through both engines:

  unbatched  every compute tier serves one task per launch
  batched    workers drain their hop queue into dynamic micro-batches
             priced ``t_fixed + n * t_marginal``, capped by the finder
             and by each member's staleness deadline

Watch three things in the output: the realized batch sizes (dynamic —
the greedy drain takes what the backlog offers, so they sit well below
the caps), the throughput/p99 pair (batching on an overloaded stream is
a Pareto win: the backlog clears faster than it grows), and the
``pinned_to_sim`` flag (the asyncio executor's batched timeline matches
the arithmetic staged replay, ``core.sim.simulate_stream``, to 1e-6).

  PYTHONPATH=src python examples/batching.py \
      [--tiers 2|3] [--overload 2.0] [--slack-stages 2.0] [--tasks 300]
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# deployment table is shared with the bench so this walkthrough always
# tells the same story the emitted BENCH_pipeline.json rows measure
from benchmarks.batching import CAP_LIMIT, DEPLOYMENTS
from repro.core.costs import segment_batch_split
from repro.core.partitioner import coach_offline_multihop
from repro.core.pipeline import plan_from_stage_times, run_pipeline
from repro.models.cnn import resnet101
from repro.serving.async_engine import run_pipeline_async
from repro.serving.batching import auto_batch_caps, realized_batch_sizes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiers", type=int, choices=(2, 3), default=2)
    ap.add_argument("--overload", type=float, default=2.0,
                    help="offered load as a multiple of the unbatched "
                         "service rate (arrivals every max_stage/overload)")
    ap.add_argument("--slack-stages", type=float, default=2.0,
                    help="staleness budget for the auto finder, in units "
                         "of the bottleneck stage time")
    ap.add_argument("--tasks", type=int, default=300)
    args = ap.parse_args()

    devices, links = DEPLOYMENTS[args.tiers]
    graph = resnet101()
    off = coach_offline_multihop(graph, devices, links)
    st = off.times
    t_fixed = tuple(
        segment_batch_split(devices[k],
                            [graph.node(i) for i in sorted(seg)])[0]
        for k, seg in enumerate(off.decision.segments(graph)))
    slack = st.max_stage * args.slack_stages
    caps = auto_batch_caps(st.compute, t_fixed, slack, CAP_LIMIT)
    period = st.max_stage / args.overload

    print(f"[deployment] {graph.name} {args.tiers}-tier over "
          f"{links[0].name}: single-task {st.latency * 1e3:.1f}ms, "
          f"bottleneck stage {st.max_stage * 1e3:.2f}ms")
    print("[split]      fixed fraction per tier: "
          + ", ".join(f"{f / c:.2f}" for f, c in zip(t_fixed, st.compute)))
    print(f"[finder]     slack {slack * 1e3:.1f}ms -> caps "
          + "/".join(str(c) for c in caps)
          + f" (limit {CAP_LIMIT})")
    print(f"[load]       {args.tasks} tasks arriving every "
          f"{period * 1e3:.2f}ms ({args.overload:.1f}x service rate)\n")

    for batched in (False, True):
        bc = list(caps) if batched else [1] * args.tiers
        plans = [plan_from_stage_times(st) for _ in range(args.tasks)]
        for p in plans:
            p.t_fixed = t_fixed
        pr = run_pipeline(plans, arrival_period=period, links=list(links),
                          batch_caps=bc)
        pa = run_pipeline_async(plans, arrival_period=period,
                                links=list(links), batch_caps=bc)
        pinned = all(abs(a.done - b.done) < 1e-6
                     for a, b in zip(pr.tasks, pa.tasks))
        label = "batched" if batched else "unbatched"
        print(f"[{label:<9}] caps " + "/".join(str(c) for c in bc)
              + " realized "
              + "/".join(f"{b:.2f}" for b in realized_batch_sizes(pr)))
        print(f"            throughput {pr.throughput:6.1f} it/s | "
              f"p99 {pr.p99_latency * 1e3:7.2f}ms | "
              f"makespan {pr.makespan * 1e3:.0f}ms | "
              f"pinned_to_sim={pinned}")


if __name__ == "__main__":
    main()
