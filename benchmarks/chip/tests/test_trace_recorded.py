"""The trace reduction on a small recorded trace: three requests of
danube3-4b at 128 tokens on one TPU v5e (``data/s128_3req.xplane.pb``,
python tracer off)."""

from pathlib import Path

import pytest

import tracing as TR

TRACE = Path(__file__).parent / "data" / "s128_3req.xplane.pb"


@pytest.fixture(scope="module")
def view():
    return TR.load(str(TRACE), ("window", "sched", "classify"))


def test_programs_per_request(view):
    for f in ("_first_forward", "_last_forward", "f", "_dequantize"):
        assert len(view.module_calls(f)) == 3, f
    assert len(view.spans["sched"]) == len(view.spans["classify"]) == 3


def test_device_work_lies_inside_its_requests(view):
    # the trace aligns the device's clock to the host's within ~0.4 ms
    tol = 1e6
    for f in ("_first_forward", "_last_forward"):
        for (s, e), (c0, c1) in zip(view.module_calls(f),
                                    view.spans["classify"]):
            assert c0 - tol <= s and e <= c1 + tol


def test_busy_share_and_gaps(view):
    w0, w1 = view.window
    busy = view.busy_in(w0, w1)
    segs = sum(e - s for f in ("_first_forward", "_last_forward")
               for s, e in view.module_calls(f))
    assert 0 < busy <= w1 - w0
    # the segment programs are most of the device's work at this size
    assert 0.8 * busy <= segs <= 1.05 * busy
    idle = sum(e - s for s, e in TR.gaps(view.busy, w0, w1))
    assert idle + busy == pytest.approx(w1 - w0)
    top = [k for k, _ in view.top_ops(10)]
    assert all(not k.split(":")[1].startswith("while") for k in top)
