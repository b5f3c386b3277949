"""The expert kernel's schedule readers, ``expert_padding`` and
``expert_early_fetch``, on hand-built ``coach.experts`` spans with the
meta ``rows`` / ``early``, and None where there is nothing to read: a
program whose spans lack the meta, a program without the spans, and a
window without the spans' requests."""

import pytest

import run
from repro.obs import served
from spec import HERE, load_module


def read(name, ctx):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "metric_" + name).read(ctx)


SHIFT = 10 ** 12


def S(name, req, parent, t0, t1, **meta):
    return served.Span(name, req, parent, t0 + SHIFT, t1 + SHIFT, meta)


# a program without the schedule meta: layer and loads only
PLAIN = [
    S(served.DECIDE, 5, None, 100, 900),
    S(served.EXPERTS, 5, 0, 700, 701, layer=0, loads=(6, 2, 0, 0)),
    S(served.DECIDE, 6, None, 1000, 1900),
    S(served.EXPERTS, 6, 3, 1700, 1701, layer=0, loads=(4, 4, 0, 0)),
]

# the same kind of layers with the kernel's schedule: rows computed, early
SCHEDULED = [
    S(served.DECIDE, 5, None, 100, 900),
    S(served.EXPERTS, 5, 0, 700, 701, layer=0, loads=(6, 2, 0, 0),
      rows=16, early=0.5),
    S(served.EXPERTS, 5, 0, 701, 702, layer=1, loads=(2, 2, 2, 2),
      rows=32, early=1.0),
    S(served.DECIDE, 6, None, 1000, 1900),
    S(served.EXPERTS, 6, 3, 1700, 1701, layer=0, loads=(4, 4, 0, 0),
      rows=16, early=0.0),
    S(served.DECIDE, 99, None, 2000, 2100),            # not in the window
    S(served.EXPERTS, 99, 5, 2050, 2051, layer=0, loads=(8, 0, 0, 0),
      rows=64, early=0.0),
]


@pytest.fixture
def span_ctx(monkeypatch):
    def make(spans, ids=(5, 6)):
        monkeypatch.setattr(served, "recorded",
                            lambda: served.Recording(tuple(spans), 0))
        return run.Context(requests=[{"id": i} for i in ids], trace=None)
    return make


def test_padding_rows_computed_over_routed(span_ctx):
    # 16 of 8, 32 of 8, 16 of 8
    got = read("expert_padding", span_ctx(SCHEDULED))
    assert got == pytest.approx((2.0 + 4.0 + 2.0) / 3)


def test_early_fetch_mean_share_in_percent(span_ctx):
    got = read("expert_early_fetch", span_ctx(SCHEDULED))
    assert got == pytest.approx((0.5 + 1.0 + 0.0) / 3 * 100)


@pytest.mark.parametrize("name", ["expert_padding", "expert_early_fetch"])
@pytest.mark.parametrize("spans,ids", [
    (PLAIN, (5, 6)),                  # a program without the meta
    ([], (5, 6)),
    (SCHEDULED, (7,)),                # a window without these requests
], ids=["no-meta", "no-spans", "empty-window"])
def test_schedule_metrics_none_without_meta(name, spans, ids, span_ctx):
    assert read(name, span_ctx(spans, ids)) is None
