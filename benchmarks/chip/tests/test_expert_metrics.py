"""The expert layer's readers, ``experts_ms``, ``experts_roofline`` and
``expert_imbalance``, on hand-built traces and spans, and None where
there is nothing to read: a program without the kernel or the spans, as
on the recorded trace of a dense program; and ``expert_cost`` against a
hand count at Mixtral's shapes."""

import json
from pathlib import Path

import pytest

import expert_cost as EC
import flops as FL
import peaks as PK
import run
import tracing as TR
from conftest import CHIP
from repro.obs import served
from spec import HERE, load_module

TRACE = Path(__file__).parent / "data" / "s128_3req.xplane.pb"
MIXTRAL = json.loads((CHIP / "configs" / "mixtral-8x7b-l4.json").read_text())
PEAK = PK.PEAKS["TPU v5 lite"]


def read(name, ctx):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "metric_" + name).read(ctx)


def view(ops):
    mods = [("_first_forward", 1000, 1200), ("_last_forward", 0, 900),
            ("other", 2000, 2100)]
    return TR.TraceView(ops, mods, {"window": [(0, 3000)],
                                    "classify": [(0, 1300), (1300, 2900)]})


OPS = [("%coach_grouped_matmul.3 = bf16[1] custom-call()", 100, 300),
       ("%fusion.1 = f32[1] fusion()", 300, 400),
       ("%coach_grouped_matmul.8 = bf16[1] custom-call()", 1100, 1160),
       ("%coach_flash_attention.2 = f32[1] custom-call()", 1160, 1190),
       ("%coach_grouped_matmul.9 = bf16[1] custom-call()", 2010, 2050)]


def ctx(trace, seq=2048):
    return run.Context(requests=[{"id": 1}, {"id": 2}], trace=trace,
                       cfg=MIXTRAL, flops=FL, peak=PEAK,
                       shapes={"seq_len": seq})


def test_kernel_time_in_the_segments_per_request():
    # 200 + 60 ns in the segments over two requests; "other" not counted
    assert read("experts_ms", ctx(view(OPS))) == pytest.approx(130e-6)


def test_roofline_share_of_the_kernel_time():
    flops, nbytes = EC.expert_cost(MIXTRAL, 2048)
    need = 4 * max(flops / PEAK["bf16_flops"],
                   nbytes / PEAK["hbm_bytes_per_s"])
    # at 1000x the hand-built times the request's kernels take 130 us
    ops = [(n, s * 1000, e * 1000) for n, s, e in OPS]
    mods = [(m, s * 1000, e * 1000) for m, s, e in view(OPS).modules]
    trace = TR.TraceView(ops, mods, {"classify": [(0, 1), (1, 2)],
                                     "window": [(0, 3e6)]})
    got = read("experts_roofline", ctx(trace))
    assert got == pytest.approx(need / 130e-6 * 100)


@pytest.mark.parametrize("name", ["experts_ms", "experts_roofline"])
def test_none_without_the_kernel(name):
    assert read(name, ctx(view(OPS[1:2] + OPS[3:4]))) is None
    recorded = TR.load(str(TRACE), ("window", "sched", "classify"))
    assert read(name, ctx(recorded)) is None


SHIFT = 10 ** 12


def S(name, req, parent, t0, t1, **meta):
    return served.Span(name, req, parent, t0 + SHIFT, t1 + SHIFT, meta)


SPANS = [
    S(served.DECIDE, 5, None, 100, 900),
    S(served.EXPERTS, 5, 0, 700, 701, layer=0, loads=(6, 2, 0, 0)),
    S(served.EXPERTS, 5, 0, 701, 702, layer=1, loads=(2, 2, 2, 2)),
    S(served.DECIDE, 6, None, 1000, 1900),
    S(served.EXPERTS, 6, 3, 1700, 1701, layer=0, loads=(4, 4, 0, 0)),
    S(served.DECIDE, 99, None, 2000, 2100),            # not in the window
    S(served.EXPERTS, 99, 5, 2050, 2051, layer=0, loads=(8, 0, 0, 0)),
]


def span_ctx(spans, monkeypatch):
    monkeypatch.setattr(served, "recorded",
                        lambda: served.Recording(tuple(spans), 0))
    return run.Context(requests=[{"id": 5}, {"id": 6}], trace=None)


def test_imbalance_busiest_over_mean(monkeypatch):
    # 6 of a mean 2, 2 of 2, 4 of 2
    got = read("expert_imbalance", span_ctx(SPANS, monkeypatch))
    assert got == pytest.approx((3.0 + 1.0 + 2.0) / 3)


@pytest.mark.parametrize("spans", [[], [s for s in SPANS
                                        if s.name == served.DECIDE]],
                         ids=["no-spans", "dense"])
def test_imbalance_none_without_expert_spans(spans, monkeypatch):
    assert read("expert_imbalance", span_ctx(spans, monkeypatch)) is None


def test_expert_cost_hand_count():
    # Mixtral-8x7B: D 4096, F 14336, 8 experts, top 2, bf16, 2048 tokens
    flops, nbytes = EC.expert_cost(MIXTRAL, 2048)
    assert flops == 2 * 4096 * 3 * 4096 * 14336 == 1_443_109_011_456
    weights = 3 * 8 * 4096 * 14336 * 2       # 2.82 GB, each read once
    rows = 4096 * 4096 * 2                    # routed rows in, outputs out
    assert nbytes == weights + 2 * rows == 2_885_681_152
    # bound by compute at a v5e's peaks: 7.33 ms of work, 3.52 ms of bytes
    assert FL.least_time(flops, nbytes, PEAK) \
        == pytest.approx(flops / 197e12)
    # the routed experts are the 87% of the layer's model FLOPs
    assert flops / FL.layer_flops(MIXTRAL, 2048) == pytest.approx(0.875,
                                                                  abs=5e-3)
