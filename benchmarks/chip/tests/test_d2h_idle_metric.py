"""The reader of device idle under the program's device-to-host pulls
(``metrics/d2h_idle_ms.py``) on hand-built spans and busy intervals: the
idle under three pulls made after the device finished, none under one
pull that waits while the device computes, overlapping pulls counted
once, and None where there is nothing to read."""

import sys

import pytest

import run
import tracing as TR
from repro.obs import served
from spec import HERE, load_module

SHIFT = 10 ** 12  # the buffer's clock less the trace's


def S(name, req, parent, t0, t1, **meta):
    return served.Span(name, req, parent, t0 + SHIFT, t1 + SHIFT, meta)


def request(req, at, pulls):
    """One request's spans from ``at`` on the trace's clock, in ns: a
    decision over [at, at + 900) whose classifier runs [at + 5, at + 795),
    and the pulls ``(parent, t0, t1, site)`` at offsets from ``at``, with
    ``parent`` an index into this list (0 the decision)."""
    out = [S(served.DECIDE, req, None, at, at + 900),
           S(served.PROBE_RESULT, req, 0, at + 200, at + 700)]
    out += [S(served.D2H, req, p, at + t0, at + t1, site=site)
            for p, t0, t1, site in pulls]
    out.append(S(served.STEP, req, 0, at + 800, at + 850))
    return out


def renumber(spans, base):
    return [s._replace(parent=None if s.parent is None else s.parent + base)
            for s in spans]


# the parent program: three pulls under ``coach.probe_result``
THREE = [(1, 200, 600, "sims"), (1, 600, 650, "best"),
         (1, 650, 700, "sep")]
# the change: one wait for the probe while the cloud segment runs
ONE = [(0, 170, 560, "probe")]


def ctx(monkeypatch, spans, ops, classify, dropped=0):
    monkeypatch.setattr(served, "recorded",
                        lambda: served.Recording(tuple(spans), dropped))
    view = TR.TraceView([(f"%fusion.{i} = f()", s, e)
                         for i, (s, e) in enumerate(ops)], [],
                        {"window": [(0, 10 ** 6)], "classify": classify})
    ids = sorted({s.req for s in spans if s.name == served.DECIDE
                  and s.req < 99})
    return run.Context(requests=[{"id": i} for i in ids], trace=view)


def read(c):
    return load_module(HERE / "metrics" / "d2h_idle_ms.py",
                       "metric_d2h_idle_ms").read(c)


def test_pulls_after_the_device_finished(monkeypatch):
    # busy [130, 580) and [640, 645): idle 20 under sims, 40 + 5 under
    # best, all 50 under sep
    c = ctx(monkeypatch, request(5, 0, THREE), [(130, 580), (640, 645)],
            [(5, 795)])
    assert read(c) == pytest.approx(115e-6)


def test_wait_beside_the_device_is_free(monkeypatch):
    c = ctx(monkeypatch, request(5, 0, ONE), [(130, 580), (640, 645)],
            [(5, 795)])
    assert read(c) == 0.0


def test_mean_over_requests_overlaps_once(monkeypatch):
    # request 6 has two pulls that overlap in [300, 400), all idle: 300
    first = request(5, 0, THREE)
    second = renumber(request(6, 1000, [(1, 200, 400, "sims"),
                                        (1, 300, 500, "best")]),
                      len(first))
    c = ctx(monkeypatch, first + second, [(130, 580), (640, 645)],
            [(5, 795), (1005, 1795)])
    assert read(c) == pytest.approx((115 + 300) / 2 * 1e-6)


@pytest.mark.parametrize("spans,dropped,classify", [
    ([], 0, [(5, 795)]),                                  # no spans
    (request(5, 0, THREE), 1, [(5, 795)]),                # buffer full
    (request(99, 0, THREE), 0, [(5, 795)]),               # not in window
    (request(5, 0, THREE), 0, []),                        # no classify
])
def test_nothing_to_read(spans, dropped, classify, monkeypatch):
    assert read(ctx(monkeypatch, spans, [(130, 580)], classify,
                    dropped)) is None


def test_program_without_spans(monkeypatch):
    # an older program, without ``repro.obs.served``
    import repro.obs
    monkeypatch.delattr(repro.obs, "served", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.served", None)
    assert read(run.Context(requests=[{"id": 5}], trace=None)) is None
