"""Served-path spans (``repro.obs.served``) through ``CoachEngine`` and
``CollabRuntime.end_step_fused`` on a tiny model: nothing is recorded
without a profiler session; under one every span of the vocabulary
appears with its nesting and request id (``coach.experts`` in a model
with expert layers, after the request's result), one probe pull per
request, each start within 50 us of its event in the written
``.xplane.pb``, and a forced collection is one ``coach.gc`` span."""

import gc
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import online as ON
from repro.core.collab import CollabRuntime
from repro.core.costs import A6000_SERVER, JETSON_NX, WIFI_5GHZ
from repro.core.schedule import StageTimes
from repro.data.pipeline import CorrelatedTaskStream
from repro.kernels import grouped_matmul as GM
from repro.models import model as M
from repro.obs import served
from repro.serving.engine import CoachEngine

N_LABELS = 4
PERIOD = 3e-3
# a dense model has no expert layers
DENSE_SPANS = set(served.SPANS) - {served.EXPERTS}


def _build(arch="h2o-danube-3-4b"):
    cfg = get_config(arch).reduced()
    rt = CollabRuntime(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                       cut_group=1)
    stream = CorrelatedTaskStream(n_labels=N_LABELS, dim=cfg.d_model,
                                  correlation="medium", seed=0)

    def tokens(task):
        return jnp.asarray((np.abs((task.features[:8] * 1000)
                                   .astype(np.int64)) % cfg.vocab_size)
                           .astype(np.int32))[None]

    calib = stream.tasks(40)
    h, _ = rt._seg_fns[0](rt.p_end, jnp.concatenate([tokens(t) for t in calib]))
    feats = np.asarray(jnp.mean(h.astype(jnp.float32), axis=1))
    st = StageTimes(T_e=2e-3, T_t=3e-3, T_c=2e-3, T_t_par=0, T_c_par=0,
                    latency=7e-3, first_tx_offset=2e-3,
                    cloud_start_offset=3e-3)
    engine = CoachEngine(rt, st, JETSON_NX, WIFI_5GHZ(20), A6000_SERVER,
                         n_labels=N_LABELS, calib_feats=feats,
                         calib_labels=np.asarray([t.label for t in calib]),
                         boundary_elems=8 * cfg.d_model)

    def classify(task):
        # the served request path: fused end hop, cloud hop, probe lift
        centers, valid = engine.sched.probe_centers()
        pkt, probe = rt.end_step_fused(tokens(task),
                                       jnp.asarray(centers, jnp.float32))
        logits = rt.cloud_step(pkt)
        pr = ON.ProbeResult.from_fused(probe.sims[0], probe.sep[0],
                                       probe.best[0], valid,
                                       n_labels=N_LABELS)
        return (np.asarray(probe.feat[0]),
                int(np.argmax(logits[0]) % N_LABELS), pr)

    return engine, stream, classify


@pytest.fixture(scope="module")
def path():
    engine, stream, classify = _build()
    engine.run_stream(stream.tasks(2), PERIOD, classify)   # compiles
    return engine, stream, classify


def _trace(path, out):
    engine, stream, classify = path
    tasks = stream.tasks(3)
    served.clear()
    with jax.profiler.trace(str(out)):
        engine.run_stream(tasks, PERIOD, classify)
        gc.disable()
        try:
            n0 = len(served.recorded().spans)
            gc.collect()
            forced = served.recorded().spans[n0:]
        finally:
            gc.enable()
    rec = served.recorded()
    served.clear()
    pb, = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    return tasks, rec, forced, pb


@pytest.fixture(scope="module")
def traced(path, tmp_path_factory):
    return _trace(path, tmp_path_factory.mktemp("trace"))


def test_off_records_nothing(path):
    engine, stream, classify = path
    served.clear()
    engine.run_stream(stream.tasks(2), PERIOD, classify)
    gc.collect()
    assert served.recorded() == ((), 0)


def test_spans_nest_under_their_request(traced):
    tasks, rec, _, _ = traced
    spans = rec.spans
    assert rec.dropped == 0
    assert DENSE_SPANS <= {s.name for s in spans}
    assert all(s.t1 is not None and s.t0 <= s.t1 for s in spans)
    for s in spans:
        if s.parent is not None:
            p = spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    ids = [t.id for t in tasks]
    decides = [i for i, s in enumerate(spans) if s.name == served.DECIDE]
    assert [spans[i].req for i in decides] == ids
    inside = {served.STEP, served.PROBE_CENTERS, served.SEGMENT,
              served.BOUNDARY, served.DEQUANTIZE, served.PROBE_RESULT,
              served.D2H}
    for task, d in zip(tasks, decides):
        mine = [s for s in spans if s.req == task.id and s.name != served.GC]
        for s in mine:
            if s.name in inside:
                assert s.parent == d, s
            else:
                assert s.name in (served.DECIDE, served.PLAN,
                                  served.ACCOUNT) and s.parent is None
        names = [s.name for s in mine]
        assert [s.meta["site"] for s in mine
                if s.name == served.D2H] == ["probe"]
        assert [s.meta["k"] for s in mine
                if s.name == served.SEGMENT] == [0, 1]
        for name in (served.STEP, served.PLAN, served.ACCOUNT,
                     served.PROBE_CENTERS, served.BOUNDARY,
                     served.DEQUANTIZE, served.PROBE_RESULT):
            assert names.count(name) == 1, name


def _matches_profiler_trace(rec, pb, names):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(pb)
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    events = {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in served.SPANS:
                        events.setdefault(e.name, []).append(e.start_ns)
    for name in names:
        mine = sorted(s.t0 - start for s in rec.spans if s.name == name)
        theirs = sorted(events.get(name, []))
        assert len(mine) == len(theirs) > 0, name
        assert np.max(np.abs(np.subtract(mine, theirs))) <= 50e3, name


def test_memory_copy_matches_profiler_trace(traced):
    _, rec, _, pb = traced
    _matches_profiler_trace(rec, pb, DENSE_SPANS)


def test_expert_loads_per_layer_after_the_result(tmp_path):
    """A model with expert layers: one ``coach.experts`` span per layer
    and request, under its decision and after the probe's pulls, whose
    loads count every token's top-k assignments."""
    engine, stream, classify = _build("mixtral-8x7b")
    cfg = engine.rt.cfg
    engine.run_stream(stream.tasks(2), PERIOD, classify)   # compiles
    tasks, rec, _, pb = _trace((engine, stream, classify), tmp_path)
    spans = rec.spans
    _matches_profiler_trace(rec, pb, set(served.SPANS))
    for task in tasks:
        mine = [s for s in spans if s.req == task.id]
        decide = next(i for i, s in enumerate(spans)
                      if s.name == served.DECIDE and s.req == task.id)
        pulled = max(s.t1 for s in mine if s.name == served.PROBE_RESULT)
        experts = [s for s in mine if s.name == served.EXPERTS]
        assert [s.meta["layer"] for s in experts] \
            == list(range(cfg.num_layers))
        for s in experts:
            assert s.parent == decide and s.t0 >= pulled
            assert len(s.meta["loads"]) == cfg.num_experts
            assert sum(s.meta["loads"]) == 8 * cfg.experts_per_token
            assert (s.meta["rows"], s.meta["early"]) == GM.fetch_schedule(
                GM.padded(np.asarray(s.meta["loads"])))
            assert s.meta["rows"] >= sum(s.meta["loads"])


def test_forced_collection_is_one_gc_span(traced):
    _, _, forced, _ = traced
    assert [(s.name, s.meta["generation"]) for s in forced] \
        == [(served.GC, 2)]


def test_full_buffer_counts_dropped(monkeypatch, tmp_path):
    monkeypatch.setattr(served, "MAX_SPANS", 2)
    served.clear()
    with jax.profiler.trace(str(tmp_path)):
        gc.disable()
        try:
            with served.span(served.DECIDE, req=7):
                with served.span(served.STEP):
                    with served.span(served.PLAN):
                        pass
                with served.span(served.ACCOUNT):
                    pass
        finally:
            gc.enable()
    rec = served.recorded()
    served.clear()
    assert rec.dropped == 2
    assert [(s.name, s.req, s.parent) for s in rec.spans] \
        == [(served.DECIDE, 7, None), (served.STEP, 7, 0)]
