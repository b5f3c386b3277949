"""Replicated-tier scale-out walkthrough: one overloaded stream, a
replicas-vs-p99 table.

ResNet101 is cut by the offline planner onto the 2-tier (Jetson-NX +
A6000; pass ``--tiers 3`` for the +AGX-Orin chain) deployment over fast
rack fabric, then every compute tier is replicated ``m``-fold
(``core.sim.PoolSpec``) behind a router policy (``serving.routing``)
and the same 4x-overloaded arrival stream is replayed per (policy, m).

Watch three things in the output: throughput scaling near-linearly in
``m`` until the serial wire binds, the p99 collapsing as queueing
drains (the scale-out Pareto win), and the informed policies (jsq, po2)
beating the random baseline — at ``m = 2`` po2 probes both replicas and
*is* JSQ; the gap opens at ``m = 4``.  The ``pinned_to_sim`` flag
confirms the per-replica asyncio executor's timeline matches the
arithmetic staged replay (``core.sim.simulate_stream``); ``m = 1`` is
the serial chain, a pool of one replica per tier.

  PYTHONPATH=src python examples/replicated_tiers.py \
      [--tiers 2|3] [--overload 4.0] [--tasks 240] [--policies jsq,po2]
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# deployment table is shared with the bench so this walkthrough always
# tells the same story the emitted BENCH_pipeline.json rows measure
from benchmarks.routing import DEPLOYMENTS, M_SWEEP, ROUTER_SEED
from repro.core.partitioner import coach_offline_multihop
from repro.core.pipeline import plan_from_stage_times, run_pipeline
from repro.models.cnn import resnet101
from repro.serving.async_engine import run_pipeline_async
from repro.serving.routing import make_router


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiers", type=int, choices=(2, 3), default=2)
    ap.add_argument("--overload", type=float, default=4.0,
                    help="offered load as a multiple of the m=1 "
                         "bottleneck rate (arrivals every "
                         "max_stage/overload)")
    ap.add_argument("--tasks", type=int, default=240)
    ap.add_argument("--policies", default="jsq,po2,random")
    args = ap.parse_args()

    graph = resnet101()
    devices, links = DEPLOYMENTS[args.tiers]
    off = coach_offline_multihop(graph, devices, links)
    st = off.times
    period = st.max_stage / args.overload
    plans = [plan_from_stage_times(st) for _ in range(args.tasks)]

    print(f"{graph.name} on {args.tiers} tiers | "
          f"stages {[round(c * 1e3, 2) for c in st.compute]} ms, "
          f"wire {[round(t * 1e3, 2) for t in st.link]} ms | "
          f"arrivals every {period * 1e3:.2f} ms "
          f"({args.overload:.1f}x overload)\n")
    hdr = (f"{'policy':>8} {'m':>3} {'throughput/s':>13} {'speedup':>8} "
           f"{'p99 ms':>9} {'mean ms':>9} {'pinned_to_sim':>14}")
    print(hdr)
    print("-" * len(hdr))
    for policy in args.policies.split(","):
        base = None
        for m in M_SWEEP:
            pools = [m] * args.tiers
            pr = run_pipeline(plans, arrival_period=period,
                              links=list(links), pools=pools,
                              router=make_router(policy, seed=ROUTER_SEED))
            pa = run_pipeline_async(plans, arrival_period=period,
                                    links=list(links), pools=pools,
                                    router=make_router(policy,
                                                       seed=ROUTER_SEED))
            pinned = abs(pr.makespan - pa.makespan) < 1e-6 and all(
                abs(a.done - b.done) < 1e-6
                for a, b in zip(pr.tasks, pa.tasks))
            base = base or pr.throughput
            print(f"{policy:>8} {m:>3} {pr.throughput:>13.1f} "
                  f"{pr.throughput / base:>7.2f}x "
                  f"{pr.p99_latency * 1e3:>9.2f} "
                  f"{pr.mean_latency * 1e3:>9.2f} {str(pinned):>14}")
        print()


if __name__ == "__main__":
    main()
