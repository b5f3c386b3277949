"""Request path: COACH's two-tier split, one request at a time.

Per request this is the body of ``CoachEngine.run_stream``
(``serving/engine.py``) with ``launch/serve.py``'s ``classify``: the engine
decides (``decide`` -> ``plan_for`` -> ``account``); inside ``decide`` the
classifier runs the end segment with the fused Pallas boundary pass
(quantize, pack, semantic probe), the cloud tier dequantizes (Pallas) and
finishes, and the argmax comes back to the host.  ``run_pipeline``, the
virtual-clock replay, is not called: it is not served work.  Both tiers
sit on one chip and the packet stays in device memory; the link's
bandwidth enters only as the engine's decision input, as in ``serve()``.

The set-up goes through the program's API: ``coach_offline`` on
``transformer_graph`` picks the cut, the weights are made from the seed
and split per segment in one jitted program, then ``CollabRuntime``,
calibration at the cell's own prompt length, and ``CoachEngine``.

This glue is copied from the program (``serve()`` builds only from an
arch id and exposes no per-request entry), so its host cost is frozen
here until the program offers one.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from traffic.generator import TaskStream, tokens

# tokens per calibration chunk: 300 prompts of 2048 tokens do not fit
# through the end segment at once beside 12 GB of weights
CALIB_TOKENS = 4096


class Driver:
    def __init__(self, run):
        from repro.core.collab import CollabRuntime, split_params_multi
        from repro.core.costs import (A6000_SERVER, JETSON_NX, WIFI_5GHZ,
                                      transformer_graph)
        from repro.core.partitioner import coach_offline
        from repro.serving.engine import CoachEngine

        cfg, pcfg, mix = run.cfg, run.pcfg, run.mix
        self.span = run.span
        self.seq = int(mix["seq_len"])
        self.bits = int(mix["wire_bits"])
        self.vocab = pcfg.vocab_size
        self.n_labels = int(mix["n_labels"])

        # ---- offline: partition on the cost graph at the cell's shape
        graph = transformer_graph(pcfg, batch=1, seq=self.seq)
        self.link = WIFI_5GHZ(float(mix["link_mbps"]))
        off = coach_offline(graph, JETSON_NX, A6000_SERVER, self.link)
        n_end = sum(1 for i in off.decision.end_set
                    if 0 < i <= pcfg.num_layers)
        self.cut = min(max(1, round(n_end / pcfg.group_size)),
                       pcfg.num_groups - 1)
        self.period = off.times.max_stage

        # ---- weights from the seed, split inside the program that makes
        # them: the whole tree never sits beside its segments
        t0 = time.perf_counter()
        segments = jax.jit(lambda k: W.make_segments(
            k, cfg, lambda t: split_params_multi(t, pcfg, (self.cut,))))(
                W.seed_key(run.seed))
        jax.block_until_ready(segments)
        self.weights_s = time.perf_counter() - t0
        self.rt = CollabRuntime(pcfg, segments, self.cut)

        # ---- calibration at the cell's prompt length, in fixed chunks
        t0 = time.perf_counter()
        self.stream = TaskStream(self.n_labels, pcfg.d_model,
                                 mix["correlation"], run.seed)
        calib = self.stream.tasks(int(mix["calib_tasks"]))
        rows = max(1, CALIB_TOKENS // self.seq)
        gap = jax.jit(lambda p, x: jnp.sum(
            self.rt._first_forward(p, x).astype(jnp.float32), axis=1)
            / self.seq)
        toks = np.concatenate([self._tokens(t) for t in calib])
        pad = -len(toks) % rows
        toks = np.concatenate([toks, np.repeat(toks[:1], pad, axis=0)])
        feats = np.concatenate([
            np.asarray(gap(self.rt.p_end, jnp.asarray(toks[i:i + rows])))
            for i in range(0, len(toks), rows)])[:len(calib)]
        labels = np.asarray([t.label for t in calib])
        self.calibration_s = time.perf_counter() - t0
        self._warm_task = calib[0]
        self.engine = CoachEngine(
            self.rt, off.times, JETSON_NX, self.link, A6000_SERVER,
            n_labels=self.n_labels, calib_feats=feats, calib_labels=labels,
            boundary_elems=self.seq * pcfg.d_model)
        self.acc = {"exits": 0, "wire": 0.0, "bits": [], "correct": []}
        self.served = {}

    def _tokens(self, task):
        return tokens(task, self.seq, self.vocab)

    def describe(self) -> dict:
        return {"cut_group": self.cut, "num_groups": self.rt.cfg.num_groups,
                "seq_len": self.seq, "wire_bits": self.bits,
                "weights_s": round(self.weights_s, 3),
                "calibration_s": round(self.calibration_s, 3)}

    def tasks(self, n: int):
        return self.stream.tasks(n)

    # ------------------------------------------------------------ request
    def _forward(self, inputs, centers, valid):
        from repro.core import online as ON

        pkt, probe = self.rt.end_step_fused(
            inputs, jnp.asarray(centers, jnp.float32), bits=self.bits)
        logits = self.rt.cloud_step(pkt)
        pr = ON.ProbeResult.from_fused(
            probe.sims[0], probe.sep[0], probe.best[0], valid,
            n_labels=self.n_labels)
        feat = np.asarray(probe.feat[0])
        row = np.asarray(logits[0])
        return feat, row, pr

    def classify(self, task):
        with self.span("classify"):
            inputs = jnp.asarray(self._tokens(task))
            centers, valid = self.engine.sched.probe_centers()
            feat, row, pr = self._forward(inputs, centers, valid)
            pred = int(np.argmax(row) % self.n_labels)
        self.served[task.id] = {"logits": row, "feat": feat,
                                "centers": centers, "valid": valid,
                                "best": pr.best}
        return feat, pred, pr

    def serve(self, task) -> None:
        with self.span("sched"):
            bw = self.link.bps_at(self.period * task.id)
            dec, feats, pred = self.engine.decide(task, bw, self.classify)
            _, wire_bits = self.engine.plan_for(dec, bw)
            self.engine.account(dec, feats, pred, task, wire_bits, self.acc)

    def warm_up(self) -> None:
        """Compile every program a request can run, without touching the
        engine's state (``decide`` and ``account`` would train it).  The
        probe runs against the trained centers only, so its shape grows
        by one each time a label is first trained: every count from
        today's up to ``n_labels`` is warmed."""
        centers, valid = self.engine.sched.probe_centers()
        inputs = jnp.asarray(self._tokens(self._warm_task))
        for n in range(max(len(valid), 1), self.n_labels + 1):
            rows = np.resize(centers, (n, centers.shape[1]))
            for _ in range(2):
                self._forward(inputs, rows, np.arange(n))

    def close(self) -> None:
        """Drop every device buffer the program holds."""
        self.rt = self.engine = None
