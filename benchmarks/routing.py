"""Replicated-tier scale-out: throughput vs replica count at bounded p99.

ResNet101 is partitioned with the real offline planner onto the 2-tier
(Jetson NX + A6000) and 3-tier (+ AGX-Orin mid) deployments, then every
compute tier is replicated ``m``-fold (``core.sim.PoolSpec``) behind a
router policy (``serving.routing``) and the same overloaded task stream
(arrival period = ``max_stage / OVERLOAD_X``, i.e. 4x the single-replica
bottleneck's service rate) is replayed per (policy, m):

  policy in {jsq, po2, random}   join-shortest-queue, power-of-two-
                                 choices, and the no-information random
                                 baseline the informed policies must beat
  m in {1, 2, 4}                 replicas per compute tier (m = 1 is the
                                 classic serial chain)

Both engines run every cell: ``engine = "sim"`` is the staged pool
replay (``sim.simulate_stream`` via ``core.pipeline.run_pipeline``),
``engine = "async"`` the per-replica asyncio workers behind per-pool
dispatchers on the virtual clock.  ``benchmarks/validate_bench.py``
gates the artifact: for jsq and po2 the ``m = 2`` row must deliver
>= 1.8x the ``m = 1`` throughput at equal-or-better p99 (random is
reported but not gated — its load imbalance is the point of the
comparison).

The deployments run over 40 GbE rack fabric: replication amortizes
*compute* service only, so the serial links must not bind before the
replicated tiers have scaled — on 10 GbE the ResNet boundary tensor
(~2 ms on the wire) caps 3-tier scale-out below the gate.  With the
wire at ~0.5 ms the chain stays compute-bound through m = 2 and the
wire (correctly) becomes the ceiling at m = 4, which is the honest
scale-out story: near-linear until the serial resource binds.
"""

from __future__ import annotations

from benchmarks.bench_io import emit_pipeline_rows
from benchmarks.multihop import _resource_names
from repro.core.costs import (A6000_SERVER, EDGE_AGX_ORIN, JETSON_NX,
                              LinkProfile)
from repro.core.partitioner import coach_offline_multihop
from repro.core.pipeline import plan_from_stage_times, run_pipeline
from repro.models.cnn import resnet101
from repro.serving.async_engine import run_pipeline_async
from repro.serving.routing import make_router

N_TASKS = 240
#: arrival period = max_stage / OVERLOAD_X — offered load is 4x the
#: single-replica bottleneck, so every m in M_SWEEP stays backlogged and
#: throughput measures service capacity, not the arrival process
OVERLOAD_X = 4.0
M_SWEEP = (1, 2, 4)
POLICIES = ("jsq", "po2", "random")
ROUTER_SEED = 0

ETH_40G = lambda: LinkProfile("eth-40g", 40e9)  # noqa: E731

DEPLOYMENTS = {
    2: ((JETSON_NX, A6000_SERVER), (ETH_40G(),)),
    3: ((JETSON_NX, EDGE_AGX_ORIN, A6000_SERVER),
        (ETH_40G(), ETH_40G())),
}


def _row(graph, n_tiers, engine, policy, m, pr, st) -> dict:
    comp_names, link_names = _resource_names(n_tiers - 1)
    bubbles = {name: pr.bubble_fraction(("compute", k))
               for k, name in enumerate(comp_names)}
    bubbles.update({name: pr.bubble_fraction(("link", k))
                    for k, name in enumerate(link_names)})
    return {
        "model": graph.name,
        "hops": n_tiers,
        "engine": engine,
        "policy": policy,
        "m": m,
        "pool_sizes": [m] * n_tiers,
        "single_task_ms": st.latency * 1e3,
        "mean_latency_ms": pr.mean_latency * 1e3,
        "p99_latency_ms": pr.p99_latency * 1e3,
        "throughput_its": pr.throughput,
        "makespan_ms": pr.makespan * 1e3,
        "max_stage_ms": st.max_stage * 1e3,
        "bubble_fraction": bubbles,
    }


def run_deployment(graph, n_tiers: int, n_tasks: int = N_TASKS) -> list:
    devices, links = DEPLOYMENTS[n_tiers]
    off = coach_offline_multihop(graph, devices, links)
    st = off.times
    period = st.max_stage / OVERLOAD_X
    plans = [plan_from_stage_times(st) for _ in range(n_tasks)]
    rows = []
    for policy in POLICIES:
        for m in M_SWEEP:
            pools = [m] * n_tiers
            pr = run_pipeline(
                plans, arrival_period=period, links=list(links),
                pools=pools, router=make_router(policy, seed=ROUTER_SEED))
            pa = run_pipeline_async(
                plans, arrival_period=period, links=list(links),
                pools=pools, router=make_router(policy, seed=ROUTER_SEED))
            rows += [_row(graph, n_tiers, "sim", policy, m, pr, st),
                     _row(graph, n_tiers, "async", policy, m, pa, st)]
    return rows


def run(out_dir=None, n_tasks: int = N_TASKS):
    rows = ["routing,engine,model,hops,policy,m,"
            "p99_ms,throughput_its,makespan_ms"]
    payload = []
    for n_tiers in (2, 3):
        for r in run_deployment(resnet101(), n_tiers, n_tasks=n_tasks):
            payload.append(r)
            rows.append(
                f"routing,{r['engine']},{r['model']},{r['hops']},"
                f"{r['policy']},{r['m']},"
                f"{r['p99_latency_ms']:.2f},{r['throughput_its']:.1f},"
                f"{r['makespan_ms']:.2f}")
    if out_dir is not None:
        emit_pipeline_rows(out_dir, "routing", payload)
    return rows


if __name__ == "__main__":
    print("\n".join(run(out_dir="experiments/bench")))
