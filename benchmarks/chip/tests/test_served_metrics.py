"""The readers of the program's served-path spans on hand-built spans and
busy intervals: self times, counts, the collector's pauses, the device
idle no span covers, and None where there is nothing to read."""

import pytest

import run
import tracing as TR
from repro.obs import served
from spec import HERE, load_module

SHIFT = 10 ** 12  # the buffer's clock less the trace's


def S(name, req, parent, t0, t1, **meta):
    return served.Span(name, req, parent, t0 + SHIFT, t1 + SHIFT, meta)


# request 5 on the trace's clock, in ns
SPANS = [
    S(served.DECIDE, 5, None, 100, 900),              # 0
    S(served.PROBE_CENTERS, 5, 0, 110, 120),
    S(served.SEGMENT, 5, 0, 130, 150, k=0),
    S(served.BOUNDARY, 5, 0, 150, 160),
    S(served.DEQUANTIZE, 5, 0, 160, 170),
    S(served.SEGMENT, 5, 0, 170, 190, k=1),           # 5
    S(served.PROBE_RESULT, 5, 0, 200, 700),
    S(served.D2H, 5, 6, 200, 600, site="sims"),
    S(served.D2H, 5, 6, 600, 650, site="best"),
    S(served.D2H, 5, 6, 650, 700, site="sep"),
    S(served.STEP, 5, 0, 800, 850),                   # 10
    S(served.GC, 5, 10, 810, 830, generation=0),
    S(served.PLAN, 5, None, 900, 910),
    S(served.ACCOUNT, 5, None, 910, 950),
    S(served.DECIDE, 99, None, 2000, 2100),           # not in the window
    S(served.GC, 99, None, 2100, 2200, generation=2),
]


def ctx(spans=SPANS, dropped=0, monkeypatch=None):
    monkeypatch.setattr(served, "recorded",
                        lambda: served.Recording(tuple(spans), dropped))
    # the device runs the segments until 580, then one slice at 640
    view = TR.TraceView([("%fusion.1 = f()", 130, 580),
                         ("%slice.2 = s()", 640, 645)], [],
                        {"window": [(0, 1000)], "classify": [(105, 795)]})
    return run.Context(requests=[{"id": 5}], trace=view)


def read(name, c):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "metric_" + name).read(c)


def test_self_times_and_counts(monkeypatch):
    c = ctx(monkeypatch=monkeypatch)
    # step 50 less its 20 ns pause, plan 10, account 40, probe_centers 10
    assert read("engine_self_ms", c) == pytest.approx(90e-6)
    assert read("dispatch_ms", c) == pytest.approx(60e-6)
    assert read("d2h_per_request", c) == 3
    assert read("gc_ms", c) == pytest.approx(20e-6)


def test_idle_no_span_covers(monkeypatch):
    # idle in [100, 950): [100, 130) less probe_centers' 10; [580, 640)
    # under the pulls; then [700, 800), [850, 900) between the spans
    c = ctx(monkeypatch=monkeypatch)
    assert read("idle_unspanned_ms", c) == pytest.approx(170e-6)


@pytest.mark.parametrize("name", ["engine_self_ms", "dispatch_ms",
                                  "d2h_per_request", "gc_ms",
                                  "idle_unspanned_ms"])
@pytest.mark.parametrize("spans,dropped", [([], 0), (SPANS, 1),
                                           (SPANS[14:], 0)])
def test_nothing_to_read(name, spans, dropped, monkeypatch):
    assert read(name, ctx(spans, dropped, monkeypatch)) is None


def test_program_without_spans(monkeypatch):
    # an older program, without ``repro.obs.served``
    import sys

    import repro.obs
    monkeypatch.delattr(repro.obs, "served", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.served", None)
    c = run.Context(requests=[{"id": 5}], trace=None)
    assert read("gc_ms", c) is None
