"""Multi-tenant serving walkthrough: three tenants, one shared deployment.

Several end-device task streams share a single collaborative VGG16
deployment (Jetson-NX end -> A6000 cloud over WiFi; pass ``--tiers 3``
for the end -> AGX-Orin edge -> cloud chain).  Each tenant gets its own
COACH online state (semantic cache, thresholds, bandwidth EMAs) inside a
``MultiTenantCoachEngine``; a pluggable admission policy decides which
tenant's task enters the shared ``2n+1`` resources whenever the end
tier frees up:

  interactive   sparse arrivals, tight SLO, weight 4
  batch         bursts of back-to-back tasks, loose SLO, weight 1
  steady        medium periodic arrivals, medium SLO, weight 2

Run it and compare the per-tenant tables: under FIFO a batch burst
drags the interactive tenant ~3-4x outside its SLO; weighted deficit
round-robin (WDRR) keeps every tenant inside its own SLO at the price
of the batch tenant absorbing its own burst — while the shared chain's
bubble fractions barely move (admission interleaving keeps the pipeline
work-conserving).

  PYTHONPATH=src python examples/multi_tenant.py \
      [--tiers 2|3] [--policies fifo,rr,wdrr] [--scale 1.0]
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

# deployment table and tenant mix are shared with the bench so this
# walkthrough always tells the same story the emitted rows measure
from benchmarks.multitenant import DEPLOYMENTS, _tenants
from repro.core import sim
from repro.core.partitioner import coach_offline_multihop
from repro.data.pipeline import CorrelatedTaskStream, make_calibration_set
from repro.models.cnn import vgg16
from repro.serving.tenancy import make_policy, MultiTenantCoachEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiers", type=int, choices=(2, 3), default=2)
    ap.add_argument("--policies", default="fifo,wdrr")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices, links = DEPLOYMENTS[args.tiers]
    graph = vgg16()
    off = coach_offline_multihop(graph, devices, links)
    st = off.times
    tenants = _tenants(st, args.scale)
    elems = max(1, int(st.link[0] * links[0].bandwidth_bps / 8))
    hop_bits = [int(np.mean(list(b.values()))) if b else 8
                for b in off.decision.all_hop_bits]

    stream = CorrelatedTaskStream(n_labels=30, dim=48,
                                  correlation="medium", seed=args.seed)
    feats, labels = make_calibration_set(stream, 400)

    def classify(task):
        d = np.linalg.norm(stream.mu - task.features[None], axis=1)
        return task.features, int(np.argmin(d))

    tasks = [stream.tasks(t.n_tasks) for t in tenants]
    print(f"[deployment] {graph.name} {args.tiers}-tier: "
          f"ingress {st.compute[0] * 1e3:.1f}ms, "
          f"single-task {st.latency * 1e3:.1f}ms, "
          f"objective {off.objective * 1e3:.1f}ms")
    for policy in args.policies.split(","):
        eng = MultiTenantCoachEngine(
            None, st, devices[0], links[0], devices[-1], 30, feats, labels,
            tenants, policy=policy, boundary_elems=elems, links=list(links),
            hop_bits_offline=hop_bits)
        mt = eng.run_streams([list(ts) for ts in tasks], classify)

        # differential sanity: the executor's timeline is pinned to the
        # multi-tenant event simulator replaying the same decided plans
        ref = sim.simulate_multitenant_stream(
            mt.plans, mt.arrivals,
            make_policy(policy, weights=[t.weight for t in tenants]),
            links=list(links))
        pinned = mt.order == ref.order and all(
            abs(a - b) < 1e-6 for a, b in zip(
                [r.done for r in mt.pipeline.tasks], ref.stream.done))

        pr = mt.pipeline
        print(f"\n[{policy}] worst-tenant p99 {mt.worst_tenant_p99 * 1e3:.0f}ms"
              f" | worst SLO-normalized p99 {mt.worst_tenant_norm_p99:.2f}"
              f" | min SLO attainment {mt.min_slo_attainment:.2%}"
              f" | pinned_to_sim={pinned}")
        print(f"  shared chain: makespan {pr.makespan * 1e3:.0f}ms, "
              f"end bubble {pr.bubble_fraction(('compute', 0)):.3f}, "
              f"cloud bubble {pr.bubble_fraction(('compute', args.tiers - 1)):.3f}")
        for rep in mt.reports:
            p = rep.stats.pipeline
            print(f"  {rep.spec.name:<12} w={rep.spec.weight:>3.0f} "
                  f"n={rep.spec.n_tasks:<4} "
                  f"p99 {p.p99_latency * 1e3:7.1f}ms "
                  f"(slo {rep.spec.slo_latency * 1e3:6.0f}ms, "
                  f"attained {rep.slo_attainment:7.2%}) "
                  f"thpt {p.throughput:6.1f}/s "
                  f"exits {rep.stats.exit_ratio:.2f}")


if __name__ == "__main__":
    main()
