"""Serving launcher: the COACH collaborative split (end segment -> wire
-> cloud segment) with the online scheduler in the loop.  Each request
runs one forward through the split; the end segment's boundary goes
through the fused quantize + pack + probe pass (a Pallas kernel on TPU).

  python -m repro.launch.serve [--arch h2o-danube-3-4b] [--requests 64]
  python -m repro.launch.serve --smoke --requests 24   # .reduced() widths

Full published widths are the default: random bf16 weights are made from
a seed and split per segment inside one jitted program, so the full tree
never sits on the device beside its segments.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import online as ON
from repro.core.collab import CollabRuntime, split_params_multi
from repro.core.costs import (A6000_SERVER, JETSON_NX, WIFI_5GHZ,
                              transformer_graph)
from repro.core.partitioner import coach_offline
from repro.data.pipeline import CorrelatedTaskStream
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.obs.bubbles import attribute, chain_resources
from repro.obs.export import text_summary
from repro.obs.trace import TraceRecorder
from repro.serving.engine import CoachEngine, EngineConfig, EngineStats


@dataclasses.dataclass
class Served:
    """What one ``serve`` call ran, for callers that check it further."""
    stats: EngineStats
    runtime: CollabRuntime
    engine: CoachEngine
    init_s: float     # weights made and split (compile included)
    warmup_s: float   # first request per wire precision (compiles)
    wall_s: float     # the timed request stream


def serve(arch: str, *, smoke: bool = False, requests: int = 200,
          bandwidth_mbps: float = 50.0, correlation: str = "medium",
          seed: int = 0, wire_bits: Sequence[int] = (8,),
          verbose: bool = True) -> Served:
    """``wire_bits`` is cycled over the requests as the end segment's
    boundary precision (the real wire the cloud segment dequantizes).  It
    is set by the caller, apart from the scheduler's Eq. 11 choice, which
    the engine's ``mean_bits`` and ``wire_kb/task`` model; the summary
    prints both.  ``smoke`` selects the config's ``.reduced()`` widths."""
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()

    # ---- offline component: partition + precision on the cost graph
    graph = transformer_graph(cfg, batch=1, seq=128)
    link = WIFI_5GHZ(bandwidth_mbps)
    off = coach_offline(graph, JETSON_NX, A6000_SERVER, link)
    # map the layer cut to a group boundary (embed node is id 0)
    n_end_layers = sum(1 for i in off.decision.end_set
                       if 0 < i <= cfg.num_layers)
    cut_group = min(max(1, round(n_end_layers / cfg.group_size)),
                    cfg.num_groups - 1)
    t0 = time.perf_counter()
    segments = jax.jit(lambda k: split_params_multi(
        M.init_params(cfg, k, jnp.bfloat16), cfg, (cut_group,)))(
            jax.random.PRNGKey(seed))
    jax.block_until_ready(segments)
    init_s = time.perf_counter() - t0
    rt = CollabRuntime(cfg, segments, cut_group)

    # ---- online component: semantic cache keyed on *real* boundary GAP
    # features (the exact features the fused boundary pass emits), so the
    # fused probe's Eq. 8-10 outputs are consistent with the cache state
    stream = CorrelatedTaskStream(n_labels=16, dim=cfg.d_model,
                                  correlation=correlation, seed=seed)

    def task_input(task):
        if cfg.embed_inputs:
            return jnp.asarray(np.tile(task.features[None, None, :],
                                       (1, 8, 1)), jnp.float32)
        toks = (np.abs((task.features[:8] * 1000).astype(np.int64))
                % cfg.vocab_size).astype(np.int32)
        return jnp.asarray(toks)[None]

    calib_tasks = stream.tasks(300)
    calib_inp = jnp.concatenate([task_input(t) for t in calib_tasks], axis=0)
    h_calib, _ = rt._seg_fns[0](rt.p_end, calib_inp)
    # same sum/seq_len GAP expression as kernels.boundary's epilogue
    feats = np.asarray(jnp.sum(h_calib.astype(jnp.float32), axis=1)
                       / h_calib.shape[1])
    labels = np.asarray([t.label for t in calib_tasks])
    rec = TraceRecorder()
    engine = CoachEngine(rt, off.times, JETSON_NX, link, A6000_SERVER,
                         n_labels=16, calib_feats=feats, calib_labels=labels,
                         boundary_elems=128 * cfg.d_model,
                         cfg=EngineConfig(trace=rec))

    def forward(inputs, bits):
        # fused boundary path: the end segment's forward + quantize +
        # pack + semantic probe read the boundary activation once; the
        # probe outputs (against the cache's current trained centers)
        # feed the scheduler directly instead of a second GAP/cosine pass
        centers, valid = engine.sched.probe_centers()
        pkt, probe = rt.end_step_fused(
            inputs, jnp.asarray(centers, jnp.float32), bits=bits)
        return probe, valid, rt.cloud_step(pkt)

    def classify(task):
        probe, valid, logits = forward(
            task_input(task), wire_bits[task.id % len(wire_bits)])
        pr = ON.ProbeResult.from_fused(
            probe.sims[0], probe.sep[0], probe.best[0], valid,
            n_labels=stream.n_labels)
        return (np.asarray(probe.feat[0]),
                int(np.argmax(logits[0]) % stream.n_labels), pr)

    # compile every wire precision's path before the timed stream
    t0 = time.perf_counter()
    for bits in sorted(set(wire_bits)):
        jax.block_until_ready(forward(calib_inp[:1], bits))
    warmup_s = time.perf_counter() - t0

    tasks = stream.tasks(requests)
    sent = ops.PATHS.copy()
    t0 = time.perf_counter()
    stats = engine.run_stream(tasks, arrival_period=off.times.max_stage,
                              classify=classify)
    wall = time.perf_counter() - t0
    sent = ops.PATHS - sent
    if verbose:
        pr = stats.pipeline
        print(f"arch={cfg.name} cut_group={cut_group}/{cfg.num_groups} "
              f"bits(offline)={sorted(set(off.decision.bits.values()))}")
        print(f"requests={requests} exit_ratio={stats.exit_ratio:.2%} "
              f"modeled: mean_bits={stats.mean_bits:.1f} "
              f"wire_kb/task={stats.wire_kb_per_task:.1f}")
        print("sent (wire_bits): " + " ".join(
            f"{b}b/{path}={n}" for (op, b, path), n in sorted(sent.items())
            if op == "boundary"))
        print(f"latency mean={pr.mean_latency*1e3:.2f}ms p99="
              f"{pr.p99_latency*1e3:.2f}ms thpt={pr.throughput:.1f} it/s "
              f"cloud_bubbles={pr.bubble_fraction('cloud'):.2%} "
              f"(wall {wall:.1f}s)")
        att = attribute(rec, resources=chain_resources(
            pr.n_hops, pr.pool_sizes or None))
        print("bubble attribution (why each resource idled):")
        print(text_summary(att))
    return Served(stats, rt, engine, init_s, warmup_s, wall)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS),
                    default="h2o-danube-3-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the config's .reduced() widths")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--bandwidth", type=float, default=50.0)
    ap.add_argument("--correlation", choices=("low", "medium", "high"),
                    default="medium")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, smoke=args.smoke, requests=args.requests,
          bandwidth_mbps=args.bandwidth, correlation=args.correlation)


if __name__ == "__main__":
    main()
