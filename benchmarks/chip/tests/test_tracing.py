"""The trace reduction: busy-interval union, device time per program, and
the attribution of idle gaps to the host span that held them."""

import pytest

import tracing as TR


def view():
    # two requests; times in ns.  Request 1: sched [0, 100), classify
    # [10, 90); request 2: sched [150, 250), classify [160, 240).
    ops = [("%while.3 = (s32[]) while()", 20, 50),     # holds the next two
           ("%fusion.1 = bf16[8] fusion()", 20, 40),
           ("%fusion.2 = bf16[8] fusion()", 30, 50),   # overlaps the first
           ("%custom-call = u8[8] custom-call()", 60, 65),
           ("%fusion.1 = bf16[8] fusion()", 170, 200),
           ("%custom-call = u8[8] custom-call()", 205, 210)]
    modules = [("_first_forward", 20, 50), ("f", 60, 65),
               ("_first_forward", 170, 200), ("f", 205, 210)]
    spans = {"window": [(0, 260)], "sched": [(0, 100), (150, 250)],
             "classify": [(10, 90), (160, 240)]}
    return TR.TraceView(ops, modules, spans)


def test_union_and_cover():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    merged = TR.union([(0, 10), (20, 30)])
    assert TR.covered(merged, 5, 25) == 10
    assert TR.covered(merged, 10, 20) == 0
    assert TR.gaps(merged, 0, 40) == [(10, 20), (30, 40)]


def test_busy_and_programs():
    v = view()
    assert v.busy == [(20, 50), (60, 65), (170, 200), (205, 210)]
    assert v.busy_in(*v.window) == 70
    assert v.module_calls("f") == [(60, 65), (205, 210)]
    top = dict(v.top_ops())
    assert top["_first_forward:fusion.1"] == pytest.approx(50e-9)
    assert top["f:custom-call"] == pytest.approx(10e-9)


def test_gap_attribution():
    v = view()
    labels = [v.label_at((s + e) / 2, ("classify", "sched"))
              for s, e in TR.gaps(v.busy, *v.window)]
    # [0,20) is in classify from 10: its midpoint 10 is in classify;
    # [50,60) classify; [65,170) midpoint 117.5 between requests;
    # [200,205) classify; [210,260) midpoint 235 in classify
    assert labels == ["classify", "classify", "", "classify", "classify"]
    assert v.label_at(95, ("classify", "sched")) == "sched"


def test_module_names():
    assert TR.module_name("jit__last_forward(8718043971175105524)") \
        == "_last_forward"
    assert TR.module_name("jit_f(6751972933678000646)") == "f"
    assert TR.op_name("%while.3 = (s32[]) while(...)") == "while.3"
