"""COACH collaborative execution in JAX: the model's scanned group stack is
split at one or more partition points; segment 0 runs on the "end" device,
each boundary activation is UAQ-quantized (Pallas kernel), transferred over
its hop as a ``WirePacket``, dequantized and continued on the next tier —
the last segment (the "cloud") finishes with norm + head.  The classic
end->cloud deployment is the single-cut case of the same machinery.

Two realizations:

  1. ``CollabRuntime`` — ``n_hops + 1`` jitted stage functions with an
     explicit wire format between them (one ``WirePacket`` per hop).  Runs
     anywhere (CPU tests/examples); the wire bytes are exactly what the
     cost model prices, and the online component consumes the GAP features
     computed by the fused semantic-probe kernel on the first boundary.

  2. ``make_collab_pipeline_step`` — the multi-pod SPMD form: layer groups
     sharded over the "pod" mesh axis, microbatched software pipeline where
     pod 1 completes microbatch i while pod 0 computes i+1 (Fig. 2 scheme 2),
     boundary tensors moved by ``ppermute`` after quantization.  Lowered and
     compiled in the dry-run.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import grouped_matmul as GM
from repro.kernels import ops as KOPS
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as MOE
from repro.models.config import ModelConfig
from repro.obs import served


# ---------------------------------------------------------------- splitting
def split_params_multi(params, cfg: ModelConfig,
                       cut_groups: Sequence[int]) -> List[Dict]:
    """Split stacked group params at each cut in ``cut_groups`` (strictly
    increasing group indices) into ``len(cut_groups) + 1`` per-device
    segments: segment k runs groups ``[cut_{k-1}, cut_k)``.  Segment 0 owns
    the embedding; the last segment owns final norm + head (and the tied
    embedding when the head is tied)."""
    cuts = list(cut_groups)
    assert all(0 < c < cfg.num_groups for c in cuts), cuts
    assert all(a < b for a, b in zip(cuts, cuts[1:])), "cuts must increase"
    take = lambda t, sl: jax.tree.map(lambda x: x[sl], t)
    bounds = [0] + cuts + [cfg.num_groups]
    segs: List[Dict] = [
        {"groups": take(params["groups"], slice(bounds[k], bounds[k + 1]))}
        for k in range(len(bounds) - 1)]
    segs[-1]["final_norm"] = params["final_norm"]
    if "embed" in params:
        segs[0]["embed"] = params["embed"]
        if "lm_head" not in params:  # tied head lives on the cloud too
            segs[-1]["embed"] = params["embed"]
    if "lm_head" in params:
        segs[-1]["lm_head"] = params["lm_head"]
    return segs


def split_params(params, cfg: ModelConfig, cut_group: int):
    """Classic 2-device split at ``cut_group`` (end gets [0, cut))."""
    end, cloud = split_params_multi(params, cfg, (cut_group,))
    return end, cloud


def _run_groups(groups, h, cfg: ModelConfig, positions):
    """The groups' layers over ``h``; their expert layers' per-expert
    counts go to the open ``moe.collect_loads``, (layers, E) in order.
    The scan does not slice the expert weights: each layer reads its
    own as a ``Slab`` of the whole stack (a slice would be copied)."""
    held = [{w: g["moe"][w] for w in MOE.EXPERT_WEIGHTS} if "moe" in g
            else {} for g in groups]
    scanned = tuple(dict(g, moe={k: v for k, v in g["moe"].items()
                                 if k not in held[i]}) if held[i] else g
                    for i, g in enumerate(groups))

    def group_body(hh, xs):
        index, gp = xs
        with MOE.collect_loads() as got:
            for i, spec in enumerate(cfg.pattern):
                p = gp[i]
                if held[i]:
                    p = dict(p, moe=dict(p["moe"], **{
                        w: GM.Slab(a, index) for w, a in held[i].items()}))
                hh, _, _ = M._block_full(p, hh, cfg, spec, positions,
                                         False, hh.shape[1])
        return hh, jnp.stack(got) if got else None
    n = jax.tree.leaves(groups)[0].shape[0]
    h, loads = lax.scan(group_body, h, (jnp.arange(n), scanned))
    if loads is not None:
        MOE.record_loads(loads.reshape(-1, loads.shape[-1]))
    return h


def _positions(B: int, S: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _first_forward(cfg: ModelConfig, p, inputs):
    B, S = inputs.shape[:2]
    h = M._embed({**p}, cfg, inputs)
    return _run_groups(p["groups"], h, cfg, _positions(B, S))


def _mid_forward(cfg: ModelConfig, p, h):
    B, S = h.shape[:2]
    return _run_groups(p["groups"], h, cfg, _positions(B, S))


def _last_forward(cfg: ModelConfig, p, h):
    B, S = h.shape[:2]
    h = _run_groups(p["groups"], h, cfg, _positions(B, S))
    h = L.rms_norm(h, p["final_norm"], cfg.norm_eps)
    return M._lm_head(p, cfg, h[:, -1])


def _program(fn, cfg: ModelConfig):
    """``fn``'s jitted program for ``cfg``, under ``fn``'s name, returning
    ``(out, loads)``: the per-expert token counts of its expert layers,
    (layers, E) int32, or None where it has none.  It holds no runtime,
    so a runtime's weights go with its last reference."""
    def program(p, x):
        with MOE.collect_loads() as got:
            out = fn(cfg, p, x)
        return out, jnp.concatenate(got) if got else None
    program.__name__ = program.__qualname__ = fn.__name__
    return jax.jit(program)


# ---------------------------------------------------------------- runtime
@dataclasses.dataclass
class WirePacket:
    """Quantized boundary activation as transmitted over one hop."""
    payload: jnp.ndarray  # uint8 (B,S,ceil(D*bits/8))
    scale: jnp.ndarray
    zp: jnp.ndarray
    bits: int
    hop: int = 0  # which link this packet crosses (0 = end's uplink)
    # true channel count when the 4-bit payload carries an odd-D
    # zero-nibble pad (None = the payload width is exact)
    channels: Optional[int] = None

    @property
    def wire_bytes(self) -> int:
        return (self.payload.size + self.scale.size * 4 + self.zp.size * 4)

    def dequantize(self, out_dtype=jnp.float32) -> jnp.ndarray:
        return KOPS.dequantize_activation(
            self.payload, self.scale, self.zp, self.bits,
            out_dtype=out_dtype, channels=self.channels)


class BoundaryProbe:
    """Semantic-probe outputs of one fused boundary pass (Eq. 8-9 on the
    GAP feature, computed in the same HBM read that quantized the wire
    packet), as host data.  ``best`` indexes into the ``centers`` matrix
    the pass was given (the caller's trained-center view, not the full
    label space).

    The probe starts the host copy of each device output when it is made,
    right after the pass is dispatched, so the copies land while the next
    tier computes.  The first read of any field waits for all four at
    once (one ``coach.d2h`` span, site ``probe``); every field is then a
    numpy array that keeps the batch dimension."""

    __slots__ = ("_out", "_host")

    def __init__(self, feat, sep, best, sims):
        self._out = (feat, sep, best, sims)
        self._host = None
        for x in self._out:
            x.copy_to_host_async()

    def _fetch(self) -> tuple:
        if self._host is None:
            if served.on():
                with served.span(served.D2H, site="probe"):
                    self._host = jax.device_get(self._out)
            else:
                self._host = jax.device_get(self._out)
            self._out = None
        return self._host

    @property
    def feat(self) -> np.ndarray:
        """(B, D) GAP features (feeds Eq. 7 center updates)."""
        return self._fetch()[0]

    @property
    def sep(self) -> np.ndarray:
        """(B,) task separability (Eq. 9)."""
        return self._fetch()[1]

    @property
    def best(self) -> np.ndarray:
        """(B,) int32 argmax similarity (Eq. 10)."""
        return self._fetch()[2]

    @property
    def sims(self) -> np.ndarray:
        """(B, L) similarity degrees in [0, 1] (Eq. 8)."""
        return self._fetch()[3]


class CollabRuntime:
    """Staged executor for one model + (multi-)partition decision.

    ``cut_group`` may be a single group index (classic end->cloud split)
    or an increasing sequence of indices (end -> edge tiers -> cloud, one
    ``WirePacket`` per hop).  ``default_bits`` is likewise an int or a
    per-hop sequence.

    ``params`` is the full parameter tree, or the per-segment list that
    ``split_params_multi`` returns for these cuts: splitting inside the
    jitted program that makes the weights means the full tree never sits
    on the device beside its segments (``repro.launch.serve``)."""

    def __init__(self, cfg: ModelConfig, params,
                 cut_group: Union[int, Sequence[int]],
                 default_bits: Union[int, Sequence[int]] = 8):
        self.cfg = cfg
        self.cuts: Tuple[int, ...] = tuple(cut_group) \
            if isinstance(cut_group, (tuple, list)) else (int(cut_group),)
        self.cut = self.cuts[0]
        bits = tuple(default_bits) \
            if isinstance(default_bits, (tuple, list)) else \
            (int(default_bits),) * self.n_hops
        assert len(bits) == self.n_hops, "need one default_bits per hop"
        self.default_bits_per_hop = bits
        self.default_bits = bits[0]
        self.p_segments = params if isinstance(params, list) \
            else split_params_multi(params, cfg, self.cuts)
        assert len(self.p_segments) == self.n_segments
        # each segment's program returns (output, expert loads)
        self._seg_fns = (
            [_program(_first_forward, cfg)]
            + [_program(_mid_forward, cfg)] * (self.n_hops - 1)
            + [_program(_last_forward, cfg)])
        # the model's index of each expert layer, by segment
        bounds = (0,) + self.cuts + (cfg.num_groups,)
        self._expert_layers = [
            [g * cfg.group_size + i for g in range(bounds[k], bounds[k + 1])
             for i, spec in enumerate(cfg.pattern) if spec.moe]
            for k in range(self.n_segments)]
        self._probe = KOPS.probe_cache

    @property
    def n_hops(self) -> int:
        return len(self.cuts)

    @property
    def n_segments(self) -> int:
        return self.n_hops + 1

    # classic 2-segment views
    @property
    def p_end(self):
        return self.p_segments[0]

    @property
    def p_cloud(self):
        return self.p_segments[-1]

    @property
    def _end_fn(self):
        return lambda p, x: self._seg_fns[0](p, x)[0]

    @property
    def _cloud_fn(self):
        return lambda p, h: self._seg_fns[-1](p, h)[0]

    # ---- the end and cloud forwards, as traced into ``_seg_fns``
    def _first_forward(self, p, inputs):
        return _first_forward(self.cfg, p, inputs)

    def _last_forward(self, p, h):
        return _last_forward(self.cfg, p, h)

    def _quantize(self, h, hop: int, bits: Optional[int]) -> WirePacket:
        bits = bits or self.default_bits_per_hop[hop]
        payload, scale, zp = KOPS.quantize_activation(h, bits)
        return WirePacket(payload, scale, zp, bits, hop=hop,
                          channels=h.shape[-1])

    def segment_step(self, k: int, x, bits: Optional[int] = None,
                     centers=None):
        """Run segment ``k``.  ``x`` is the raw model input for ``k = 0``,
        else the ``WirePacket`` delivered over hop ``k-1``.  Intermediate
        segments return ``(WirePacket for hop k, boundary activation)``;
        the last segment returns the logits.

        ``centers`` (an (L, D) trained-center matrix) switches an
        intermediate segment to the *fused* boundary path: quantize +
        pack + semantic probe in a single HBM read of the boundary
        activation (``kernels.boundary``), returning ``(WirePacket,
        BoundaryProbe)`` instead — the probe outputs replace the raw
        activation, so nothing re-reads it."""
        if k > 0:
            assert isinstance(x, WirePacket) and x.hop == k - 1, \
                f"segment {k} consumes the hop-{k - 1} packet"
            # the receiving tier continues in its own weights' dtype
            x = x.dequantize(jax.tree.leaves(self.p_segments[k])[0].dtype)
        if served.on():
            with served.span(served.SEGMENT, k=k):
                h, loads = self._seg_fns[k](self.p_segments[k], x)
            if loads is not None:
                served.hold_loads(self._expert_layers[k], loads)
        else:
            h, _ = self._seg_fns[k](self.p_segments[k], x)
        if k == self.n_hops:
            return h
        if centers is not None:
            bits = bits or self.default_bits_per_hop[k]
            payload, scale, zp, feat, sep, best, sims = \
                KOPS.boundary_pass(h, centers, bits)
            pkt = WirePacket(payload, scale, zp, bits, hop=k,
                             channels=self.cfg.d_model)
            return pkt, BoundaryProbe(feat, sep, best, sims)
        return self._quantize(h, k, bits), h

    def segment_handle(self, k: int, probe_centers=None, on_probe=None):
        """Bound per-segment callable for hop-queue workers.

        Worker ``k`` applies the handle to the payload it dequeued (the
        raw model input for ``k = 0``, else the hop-``k-1`` ``WirePacket``)
        and forwards the result: intermediate segments yield the hop-``k``
        packet, the last segment yields the logits.

        ``probe_centers`` (a zero-arg callable returning the current
        trained-center matrix for this boundary) switches intermediate
        segments to the fused single-read path; each pass's
        ``BoundaryProbe`` is delivered through ``on_probe(k, probe)`` —
        the forwarded payload stays the plain ``WirePacket`` the next
        hop-queue worker expects."""
        assert 0 <= k <= self.n_hops, k

        def handle(x, bits: Optional[int] = None):
            if probe_centers is not None and k < self.n_hops:
                pkt, probe = self.segment_step(k, x, bits=bits,
                                               centers=probe_centers())
                if on_probe is not None:
                    on_probe(k, probe)
                return pkt
            out = self.segment_step(k, x, bits=bits)
            return out[0] if isinstance(out, tuple) else out

        return handle

    # ---- stage A (end device / pod 0)
    def end_step(self, inputs, bits: Optional[int] = None
                 ) -> Tuple[WirePacket, jnp.ndarray]:
        """Returns (hop-0 wire packet, boundary activation pre-quant)."""
        return self.segment_step(0, inputs, bits=bits)

    def end_step_fused(self, inputs, centers, bits: Optional[int] = None
                       ) -> Tuple[WirePacket, BoundaryProbe]:
        """Fused end step: forward + quantize + pack + semantic probe
        with a single HBM read of the boundary activation.  Returns the
        hop-0 wire packet and the probe outputs (GAP feature included),
        instead of the raw activation the classic ``end_step`` hands
        back for a second probe read.  The probe is host data, fetched
        while the next tier computes (``BoundaryProbe``)."""
        return self.segment_step(0, inputs, bits=bits, centers=centers)

    def probe(self, h, centers):
        """Fused GAP+cosine+separability on the boundary activation."""
        return self._probe(h, centers)

    # ---- stage B (cloud / last segment); classic path keeps working for
    # single-cut runtimes, and for multi-cut ones this relays the packet
    # through the remaining tiers.
    def cloud_step(self, packet: WirePacket) -> jnp.ndarray:
        out = packet
        for k in range(packet.hop + 1, self.n_segments):
            out = self.segment_step(k, out)
            if isinstance(out, tuple):
                out = out[0]
        return out

    def run(self, inputs, bits: Optional[Sequence[Optional[int]]] = None):
        """Full multi-hop forward: returns (logits, per-hop packets)."""
        bits = tuple(bits) if bits is not None else (None,) * self.n_hops
        assert len(bits) == self.n_hops
        packets: List[WirePacket] = []
        pkt, _ = self.segment_step(0, inputs, bits=bits[0])
        packets.append(pkt)
        for k in range(1, self.n_hops):
            pkt, _ = self.segment_step(k, pkt, bits=bits[k])
            packets.append(pkt)
        logits = self.segment_step(self.n_segments - 1, pkt)
        return logits, packets

    # ---- reference: monolithic forward (accuracy-loss measurement)
    def monolithic(self, params, inputs):
        h, _, _ = M.forward(params, self.cfg, inputs)
        return M._lm_head(params, self.cfg, h[:, -1])


# ------------------------------------------------------- multi-pod pipeline
def make_collab_pipeline_step(cfg: ModelConfig, mesh, *, bits: int = 8,
                              n_micro: int = 2):
    """SPMD two-pod software pipeline (dry-run artifact).

    params["groups"] leaves are sharded P("pod", ...) — the end pod owns the
    first half of the layer groups, the cloud pod the second half.  Each
    pipeline tick: every pod runs its local groups on its current
    microbatch, then the boundary activation is UAQ-quantized and
    ``ppermute``d pod0 -> pod1 while pod 0 starts the next microbatch
    (near bubble-free: the transfer overlaps compute, Fig. 2 scheme 3).
    """
    from jax.sharding import PartitionSpec as P

    assert "pod" in mesh.axis_names, "multi-pod mesh required"

    def local_groups_fwd(groups, h, positions):
        return _run_groups(groups, h, cfg, positions)

    def step(params, tokens):
        """tokens: (n_micro, B_mb, S) int32 (or embeds (..., D))."""
        B_mb, S = tokens.shape[1], tokens.shape[2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B_mb, S))

        dt = jax.tree.leaves(params["groups"])[0].dtype

        def spmd(groups, tok):
            pod = lax.axis_index("pod")
            n_ticks = n_micro + 1
            h_buf = jnp.zeros((B_mb, S, cfg.d_model), dt)
            outs = jnp.zeros((n_micro, B_mb, S, cfg.d_model), dt)

            def tick(t, carry):
                h_recv, outs = carry
                mb = jnp.clip(t, 0, n_micro - 1)
                tok_mb = tok[mb]
                # pod 0 embeds its (current) microbatch; pod 1 continues
                # from the dequantized boundary activation it received
                h0 = M._embed(params, cfg, tok_mb).astype(dt)
                h_in = jnp.where(pod == 0, h0, h_recv)
                h = local_groups_fwd(groups[0], h_in, positions)
                # quantize boundary + move across the pod axis through
                # the shared trace-safe wire entry (KOPS.wire_*): the
                # Pallas kernel on TPU, the exact jnp reference on
                # backends where interpret-mode Pallas cannot compile
                # inside a manual shard_map region — so the runtime,
                # this SPMD pipeline, and the bench measure one path
                flat = h.reshape(-1, cfg.d_model)
                q, sc, zp = KOPS.wire_quantize(flat, bits)
                q, sc, zp = [lax.ppermute(x, "pod", [(0, 1)])
                             for x in (q, sc, zp)]
                h_next = KOPS.wire_dequantize(
                    q, sc, zp, bits, out_dtype=dt, channels=cfg.d_model
                ).reshape(B_mb, S, cfg.d_model)
                done = jnp.where(pod == 1, h, jnp.zeros_like(h))
                outs = lax.dynamic_update_index_in_dim(
                    outs, done, jnp.clip(t - 1, 0, n_micro - 1), 0)
                return (h_next, outs)

            h_recv, outs = lax.fori_loop(0, n_ticks, tick, (h_buf, outs))
            # pod 0 holds zeros; reduce so the (replicated) output is pod 1's
            return lax.psum(outs, "pod")

        fn = jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P("pod"), P()),
            out_specs=P(),
            check_vma=False,
            axis_names=frozenset({"pod"}),
        )
        # final norm + head on the pipeline output (cloud side)
        h = fn((params["groups"],), tokens)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return M._lm_head(params, cfg, h[:, :, -1])

    return step
