"""``attention_ms`` on hand-built traces, and on the recorded trace of a
program that runs no flash kernel."""

from pathlib import Path

import pytest

import run
import tracing as TR
from spec import HERE, load_module

TRACE = Path(__file__).parent / "data" / "s128_3req.xplane.pb"


def read(view):
    mod = load_module(HERE / "metrics" / "attention_ms.py", "metric_attn")
    return mod.read(run.Context(requests=[], trace=view))


def test_kernel_time_in_the_segments_per_request():
    ops = [("%coach_flash_attention.6 = f32[1] custom-call()", 100, 200),
           ("%fusion.1 = f32[1] fusion()", 200, 300),
           ("%coach_flash_attention.2 = f32[1] custom-call()", 1100, 1130),
           ("%coach_flash_attention.9 = f32[1] custom-call()", 2010, 2050)]
    mods = [("_first_forward", 1000, 1200), ("_last_forward", 0, 900),
            ("other", 2000, 2100)]
    view = TR.TraceView(ops, mods, {"window": [(0, 3000)],
                                    "classify": [(0, 1300), (1300, 2900)]})
    # 100 + 30 ns in the segments over two requests; the call in "other"
    # is not counted
    assert read(view) == pytest.approx(65e-6)


def test_none_without_the_kernel():
    view = TR.TraceView([("%fusion.1 = f()", 100, 200)],
                        [("_last_forward", 0, 900)],
                        {"window": [(0, 1000)], "classify": [(0, 950)]})
    assert read(view) is None
    recorded = TR.load(str(TRACE), ("window", "sched", "classify"))
    assert read(recorded) is None
