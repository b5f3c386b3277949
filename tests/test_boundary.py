"""Fused boundary pass: kernel vs exact-jnp reference, odd-channel wire
regression, the runtime's fused hop (one HBM read serving both the wire
packet and the semantic probe), and the sim/async engine differential
with fused probe results in the decision loop.

(Deliberately hypothesis-free: unlike ``test_kernels.py`` this file also
runs on hosts without the property-testing extra installed.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import online as ON
from repro.core.collab import BoundaryProbe, CollabRuntime, WirePacket
from repro.core.costs import (A6000_SERVER, JETSON_NX, WIFI_5GHZ)
from repro.core.schedule import StageTimes
from repro.configs import get_config
from repro.data.pipeline import CorrelatedTaskStream, make_calibration_set
from repro.kernels import ops, ref
from repro.kernels.boundary import fused_boundary
from repro.models import model as M
from repro.serving.async_engine import AsyncCoachEngine
from repro.serving.engine import CoachEngine


# ------------------------------------------------------- kernel vs ref
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("B,S,D,L", [(2, 64, 32, 5), (3, 100, 33, 4),
                                     (1, 1, 16, 2)])
def test_fused_boundary_kernel_bitexact_vs_jitted_ref(B, S, D, L, bits):
    """Interpret-mode kernel == jitted exact reference, bit for bit, on
    the wire fields for every shape and on everything for single-S-block
    shapes (the ref is compared *jitted* so both sides see XLA's
    reciprocal rewrite of the division by qmax)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D)) * 2.0
    c = jax.random.normal(jax.random.PRNGKey(1), (L, D))
    out_k = fused_boundary(x, c, bits, interpret=True)
    out_r = jax.jit(lambda a, b: ref.fused_boundary_ref(a, b, bits))(x, c)
    payload, scale, zp, feat, sep, best, sims = out_k
    pr, sr, zr, fr, sep_r, best_r, sims_r = out_r
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(zp), np.asarray(zr))
    np.testing.assert_array_equal(np.asarray(best), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(feat), np.asarray(fr))
    np.testing.assert_array_equal(np.asarray(sims), np.asarray(sims_r))
    np.testing.assert_array_equal(np.asarray(sep), np.asarray(sep_r))
    assert payload.shape == (B, S, (D + 1) // 2 if bits == 4 else D)


@pytest.mark.parametrize("bits", [4, 8])
def test_boundary_pass_dispatches_to_exact_ref_off_tpu(bits):
    """The runtime entry point off-TPU *is* the jitted reference (same
    bits), so the fused path and the test oracle cannot drift."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU dispatch path")
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 48))
    c = jax.random.normal(jax.random.PRNGKey(3), (6, 48))
    got = ops.boundary_pass(x, c, bits)
    want = jax.jit(lambda a, b: ref.fused_boundary_ref(a, b, bits))(x, c)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------ odd-channel wire path
@pytest.mark.parametrize("n", [5, 33, 129])
@pytest.mark.parametrize("bits", [4, 8])
def test_wire_roundtrip_odd_channels(n, bits):
    """Regression (int4 odd channel dims): quantize -> dequantize through
    the shared entry points restores the true channel count with at most
    half a quantum of error; scale/zp are computed on the true N."""
    x = jax.random.normal(jax.random.PRNGKey(4), (8, n)) * 3.0
    p, s, z = ops.quantize_activation(x, bits, use_kernel=False)
    assert p.shape == (8, (n + 1) // 2 if bits == 4 else n)
    y = ops.dequantize_activation(p, s, z, bits, use_kernel=False,
                                  channels=n)
    assert y.shape == x.shape
    err = np.abs(np.asarray(y) - np.asarray(x))
    assert (err <= np.asarray(s) * 0.5 * (1 + 1e-3)).all()


# --------------------------------------------------- ProbeResult lifting
def test_probe_result_from_fused_scatters_to_full_label_space():
    sims = np.array([0.9, 0.2, 0.6])
    pr = ON.ProbeResult.from_fused(sims, sep=1.7, best=0,
                                   valid=np.array([3, 5, 8]), n_labels=10)
    full = np.zeros(10)
    full[[3, 5, 8]] = sims
    np.testing.assert_array_equal(pr.sims, full)
    assert pr.best == 3 and pr.sep == 1.7


def test_probe_result_from_fused_cold_cache_never_exits():
    # < 2 trained centers: no genuine second-highest degree, sep forced 0
    pr = ON.ProbeResult.from_fused(np.array([0.9]), sep=5.0, best=0,
                                   valid=np.array([4]), n_labels=6)
    assert pr.sep == 0.0 and pr.best == 4
    pr = ON.ProbeResult.from_fused(np.zeros(0), sep=5.0, best=0,
                                   valid=np.zeros(0, int), n_labels=6)
    assert pr.sep == 0.0 and pr.best == 0 and not pr.sims.any()


def test_scheduler_step_consumes_probe_result():
    """A supplied ProbeResult replaces the cache recompute: an enormous
    separability forces the exit the cache's own sims would not take,
    and sep = 0 blocks exit regardless of the features."""
    stream = CorrelatedTaskStream(n_labels=8, dim=16, correlation="high",
                                  seed=0)
    feats, labels = make_calibration_set(stream, 200)
    eng = CoachEngine(None, StageTimes(
        T_e=2e-3, T_t=3e-3, T_c=2e-3, T_t_par=0, T_c_par=0, latency=7e-3,
        first_tx_offset=2e-3, cloud_start_offset=3e-3), JETSON_NX,
        WIFI_5GHZ(20), A6000_SERVER, n_labels=8, calib_feats=feats,
        calib_labels=labels, boundary_elems=10_000)
    sched = eng.sched
    f = feats[0]
    force = ON.ProbeResult(sims=np.full(8, 0.5), sep=1e9, best=3)
    dec = sched.step(f, probe=force)
    assert dec.early_exit and dec.result == 3
    block = ON.ProbeResult(sims=np.full(8, 0.5), sep=0.0, best=3)
    dec = sched.step(f, probe=block)
    assert not dec.early_exit


# ------------------------------------------------------- runtime fused hop
def _runtime():
    cfg = get_config("gemma2-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, CollabRuntime(cfg, params, cut_group=1)


def _inputs(cfg, key, batch=2):
    if cfg.embed_inputs:
        return jax.random.normal(key, (batch, 8, cfg.d_model))
    return jax.random.randint(key, (batch, 8), 0, cfg.vocab_size, jnp.int32)


def test_end_step_fused_matches_classic_hop():
    """The fused end hop emits the same wire packet as the classic
    quantize path plus a probe consistent with the boundary activation,
    and the cloud consumes the packet identically."""
    cfg, rt = _runtime()
    x = _inputs(cfg, jax.random.PRNGKey(1))
    centers = jax.random.normal(jax.random.PRNGKey(2), (5, cfg.d_model))
    pkt_c, h = rt.segment_step(0, x)
    pkt_f, probe = rt.end_step_fused(x, centers)
    assert isinstance(pkt_f, WirePacket) and isinstance(probe, BoundaryProbe)
    assert pkt_f.channels == cfg.d_model
    np.testing.assert_array_equal(np.asarray(pkt_f.payload),
                                  np.asarray(pkt_c.payload))
    np.testing.assert_array_equal(np.asarray(pkt_f.scale),
                                  np.asarray(pkt_c.scale))
    np.testing.assert_array_equal(np.asarray(pkt_f.zp),
                                  np.asarray(pkt_c.zp))
    # probe outputs == the unfused probe of the same boundary activation
    sep_r, best_r, sims_r = ref.semantic_probe_ref(
        h.astype(jnp.float32), centers)
    np.testing.assert_array_equal(np.asarray(probe.best),
                                  np.asarray(best_r))
    np.testing.assert_allclose(np.asarray(probe.sims), np.asarray(sims_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(probe.sep), np.asarray(sep_r),
                               rtol=1e-4, atol=1e-5)
    gap = np.asarray(jnp.sum(h.astype(jnp.float32), axis=1) / h.shape[1])
    np.testing.assert_allclose(np.asarray(probe.feat), gap, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(rt.cloud_step(pkt_f)), np.asarray(rt.cloud_step(pkt_c)))


PROBE_FIELDS = ("feat", "sep", "best", "sims")


def _host_fields(probe):
    got = [getattr(probe, f) for f in PROBE_FIELDS]
    assert all(type(a) is np.ndarray for a in got)
    return got


def test_segment_handle_fused_delivers_probe():
    cfg, rt = _runtime()
    x = _inputs(cfg, jax.random.PRNGKey(3))
    centers = jax.random.normal(jax.random.PRNGKey(4), (4, cfg.d_model))
    seen = {}
    h = rt.segment_handle(0, probe_centers=lambda: centers,
                          on_probe=lambda k, p: seen.setdefault(k, p))
    pkt = h(x)
    assert isinstance(pkt, WirePacket)
    assert 0 in seen and isinstance(seen[0], BoundaryProbe)
    pkt_f, probe = rt.end_step_fused(x, centers)
    np.testing.assert_array_equal(np.asarray(pkt.payload),
                                  np.asarray(pkt_f.payload))
    for got, want in zip(_host_fields(seen[0]), _host_fields(probe)):
        np.testing.assert_array_equal(got, want)


def test_fused_probe_is_host_data_of_the_pass():
    """The probe's fields are numpy arrays equal, bit for bit, to the
    boundary pass's device outputs on the same activation and centers."""
    cfg, rt = _runtime()
    x = _inputs(cfg, jax.random.PRNGKey(5))
    centers = jax.random.normal(jax.random.PRNGKey(6), (5, cfg.d_model))
    _, h = rt.segment_step(0, x)
    want = ops.boundary_pass(h, centers, 8)[3:]
    _, probe = rt.end_step_fused(x, centers, bits=8)
    for got, ref_out in zip(_host_fields(probe), want):
        assert got.shape == ref_out.shape and got.dtype == ref_out.dtype
        np.testing.assert_array_equal(got, np.asarray(ref_out))


def test_fused_probe_host_copy_starts_at_dispatch(monkeypatch):
    """The four host copies are started before ``end_step_fused``
    returns, and the fields read back what was copied."""
    cfg, rt = _runtime()
    x = _inputs(cfg, jax.random.PRNGKey(7))
    centers = jax.random.normal(jax.random.PRNGKey(8), (3, cfg.d_model))
    rt.end_step_fused(x, centers)                       # compiles
    started = []
    array_type = type(jnp.zeros(()))
    start = array_type.copy_to_host_async
    monkeypatch.setattr(array_type, "copy_to_host_async",
                        lambda a: (started.append(a), start(a))[1])
    _, probe = rt.end_step_fused(x, centers)
    copied = list(started)
    assert len(copied) == len(PROBE_FIELDS)
    for got, dev in zip(_host_fields(probe), copied):
        np.testing.assert_array_equal(got, np.asarray(dev))


def test_fused_probe_waits_once_under_a_session(monkeypatch):
    """Under a profiler session the first field read is the one pull, a
    ``coach.d2h`` span with site ``probe``; later reads pull nothing."""
    from repro.obs import served
    cfg, rt = _runtime()
    x = _inputs(cfg, jax.random.PRNGKey(11))
    centers = jax.random.normal(jax.random.PRNGKey(12), (3, cfg.d_model))
    _, probe = rt.end_step_fused(x, centers)
    monkeypatch.setattr(served, "on", lambda: True)
    served.clear()
    try:
        _host_fields(probe)
        _host_fields(probe)
        got = [(s.name, s.meta) for s in served.recorded().spans]
    finally:
        served.clear()
    assert got == [(served.D2H, {"site": "probe"})]


@pytest.mark.parametrize("n_valid", [1, 3])
def test_probe_result_lifts_host_fields(n_valid, monkeypatch):
    """``ProbeResult.from_fused`` lifts a probe's host fields to label
    space under a profiler session without a ``coach.d2h`` span: the
    fields were fetched before it runs."""
    from repro.obs import served
    sims = np.asarray([0.25, 0.75, 0.5][:n_valid], np.float32)
    sep, best = np.float32(0.125), np.int32(n_valid - 1)
    valid = np.asarray([4, 1, 6][:n_valid])
    monkeypatch.setattr(served, "on", lambda: True)
    served.clear()
    try:
        pr = ON.ProbeResult.from_fused(sims, sep, best, valid, n_labels=8)
        names = [s.name for s in served.recorded().spans]
    finally:
        served.clear()
    want = np.zeros(8)
    want[valid] = sims
    np.testing.assert_array_equal(pr.sims, want)
    assert pr.best == valid[-1]
    assert pr.sep == (0.125 if n_valid >= 2 else 0.0)
    assert names == [served.PROBE_RESULT]


# --------------------------------------- engine differential, fused probes
def _fused_classify(stream):
    """Deterministic, engine-state-free fused-style classify: the probe
    outputs are a pure function of the task, so both engines must reach
    identical decisions from them."""
    mu = stream.mu / np.linalg.norm(stream.mu, axis=1, keepdims=True)

    def f(task):
        fn = task.features / max(np.linalg.norm(task.features), 1e-12)
        sims = (mu @ fn + 1.0) * 0.5
        order = np.argsort(-sims)
        t_h, t_sh = float(sims[order[0]]), float(sims[order[1]])
        sep = (t_h - t_sh) * t_h / max(t_sh, 1e-12)
        pr = ON.ProbeResult(sims=sims, sep=sep, best=int(order[0]))
        return task.features, int(order[0]), pr
    return f


def test_async_engine_decisions_identical_with_fused_probes():
    """Decision determinism holds with the fused probe in the loop: the
    3-tuple classify protocol yields identical sync/async EngineStats."""
    st = StageTimes(T_e=2e-3, T_t=3e-3, T_c=2e-3, T_t_par=0, T_c_par=0,
                    latency=7e-3, first_tx_offset=2e-3,
                    cloud_start_offset=3e-3)
    stream = CorrelatedTaskStream(n_labels=12, dim=32, correlation="high",
                                  seed=11)
    feats, labels = make_calibration_set(stream, 300)
    mk = lambda cls: cls(None, st, JETSON_NX, WIFI_5GHZ(20), A6000_SERVER,
                         n_labels=12, calib_feats=feats,
                         calib_labels=labels, boundary_elems=50_000)
    classify = _fused_classify(stream)
    tasks = list(stream.tasks(200))
    s = mk(CoachEngine).run_stream(list(tasks), arrival_period=3e-3,
                                   classify=classify)
    a = mk(AsyncCoachEngine).run_stream(list(tasks), arrival_period=3e-3,
                                        classify=classify)
    assert s.exit_ratio == a.exit_ratio
    assert s.mean_bits == a.mean_bits
    assert s.accuracy == a.accuracy
    assert abs(s.pipeline.makespan - a.pipeline.makespan) < 1e-6


# ------------------------------------------------ int4 wire layout, ragged
@pytest.mark.parametrize("n", [6, 5])
def test_pack4_pairs_channel_halves(n):
    """Wire format: byte i carries channel i (low nibble) and channel
    i + ceil(n/2) (high nibble); an odd n leaves the last high nibble 0.
    The kernels' pack and the reference's are written apart, so both are
    pinned to this numpy layout."""
    from repro.kernels.uaq import pack4
    q = (np.arange(n) % 16).astype(np.uint8)[None]
    h = (n + 1) // 2
    hi = np.concatenate([q[0, h:], np.zeros(2 * h - n, np.uint8)])
    want = q[0, :h] | (hi << 4)
    for pack in (ref.pack4_ref, pack4):
        packed = np.asarray(pack(jnp.asarray(q)))
        np.testing.assert_array_equal(packed[0], want)
        np.testing.assert_array_equal(
            np.asarray(ref.unpack4_ref(jnp.asarray(packed), n=n)), q)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("M", [300, 13])
def test_uaq_kernel_pads_ragged_rows(M, bits):
    """Any row count runs: rows are zero-padded to the row block and
    sliced off, leaving the real rows as the block-aligned call gives
    them, and the round trip stays within half a quantum."""
    from repro.kernels.uaq import uaq_dequantize, uaq_quantize
    x = jax.random.normal(jax.random.PRNGKey(5), (512, 66)) * 2.0
    p, s, z = uaq_quantize(x[:M], bits, interpret=True)
    pa, sa, za = uaq_quantize(x, bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(pa[:M]))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sa[:M]))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(za[:M]))
    y = uaq_dequantize(p, s, z, bits, n=66, interpret=True)
    assert y.shape == (M, 66)
    err = np.abs(np.asarray(y) - np.asarray(x[:M]))
    assert (err <= np.asarray(s) * 0.5 * (1 + 1e-3)).all()


def test_wire_calls_counted_by_path():
    """``ops.PATHS`` records which path every wire call took: 4/8 bits
    with the kernel on are Pallas (compiled on TPU, interpret mode
    elsewhere), other widths and off-TPU fused passes the jnp
    reference."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 32))
    c = jax.random.normal(jax.random.PRNGKey(7), (3, 32))
    before = ops.PATHS.copy()
    ops.quantize_activation(x, 8)
    ops.quantize_activation(x, 6)
    p, s, z = ops.quantize_activation(x, 4, use_kernel=False)
    ops.dequantize_activation(p, s, z, 4, channels=32)
    ops.boundary_pass(x, c, 8)
    got = ops.PATHS - before
    tpu = jax.default_backend() == "tpu"
    kernel = "pallas" if tpu else "interpret"
    assert got == {("quantize", 8, kernel): 1, ("quantize", 6, "ref"): 1,
                   ("quantize", 4, "ref"): 1,
                   ("dequantize", 4, kernel): 1,
                   ("boundary", 8, "pallas" if tpu else "ref"): 1}


def test_runtime_from_presplit_bf16_segments():
    """The runtime takes the per-segment list ``split_params_multi``
    returns (made under one jit, as ``repro.launch.serve`` does) and the
    receiving tier continues in the weights' dtype."""
    from repro.core.collab import split_params_multi
    cfg = get_config("h2o-danube-3-4b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    segs = jax.jit(lambda p: split_params_multi(p, cfg, (1,)))(params)
    rt_s = CollabRuntime(cfg, segs, cut_group=1)
    rt_p = CollabRuntime(cfg, params, cut_group=1)
    x = _inputs(cfg, jax.random.PRNGKey(8))
    pkt, _ = rt_s.end_step(x, bits=4)
    out = rt_s.cloud_step(pkt)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(
        rt_p.cloud_step(rt_p.end_step(x, bits=4)[0]), np.float32))
