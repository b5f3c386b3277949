"""The FLOP and byte functions against the hand counts in PERF.md."""

import json

import pytest

import flops as FL
from conftest import CHIP

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def cfg(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_danube_request_flops():
    c = cfg("danube3-4b")
    # per layer at 128 tokens: projections 2*128*3840*(3840+2*960)
    # + 2*128*3840*3840, attention 2*2*128*(129/2)*32*120, MLP
    # 2*128*3*3840*10240; 24 layers plus a 3840 x 32000 head
    proj = 2 * 128 * 3840 * 5760 + 2 * 128 * 3840 * 3840
    attn = 4 * 128 * 64.5 * 3840
    mlp = 6 * 128 * 3840 * 10240
    want = 24 * (proj + attn + mlp) + 2 * 3840 * 32000
    assert FL.request_flops(c, 128) == pytest.approx(want, rel=1e-12)
    assert FL.request_flops(c, 128) == pytest.approx(0.955e12, rel=2e-3)
    assert FL.request_flops(c, 2048) == pytest.approx(16.02e12, rel=2e-3)


def test_mixtral_request_flops_count_routed_experts():
    c = cfg("mixtral-8x7b-l4")
    per_layer = (2 * 2048 * 4096 * (4096 + 2048) + 2 * 2048 * 4096 * 4096
                 + 4 * 2048 * 1024.5 * 4096
                 + 2 * 2048 * 2 * 3 * 4096 * 14336   # top-2 of 8 experts
                 + 2 * 2048 * 4096 * 8)               # router
    want = 4 * per_layer + 2 * 4096 * 32000
    assert FL.request_flops(c, 2048) == pytest.approx(want, rel=1e-12)
    assert FL.request_flops(c, 2048) == pytest.approx(6.60e12, rel=5e-3)


def test_attention_span_window():
    assert FL.attention_span(4, 8) == pytest.approx(2.5)
    # queries 0..1 see 1 and 2 keys, queries 2..3 see 2 (window 2)
    assert FL.attention_span(4, 2) == pytest.approx((1 + 2 + 2 + 2) / 4)


def test_wire_kernel_bytes():
    # fused boundary at (1, 2048, 3840) bf16, 16 centers, 8 bits: read the
    # activation and the centers, write payload, scale, zero-point, feature,
    # sep, best and sims
    flops, nbytes = FL.boundary_cost(2048, 3840, 16, 8)
    want = (2048 * 3840 * 2 + 16 * 3840 * 4) + (2048 * 3840 + 2048 * 8
                                                + 3840 * 4 + 8 + 16 * 4)
    assert nbytes == want and flops == 2 * 16 * 3840
    _, nbytes4 = FL.boundary_cost(2048, 3840, 16, 4)
    assert want - nbytes4 == 2048 * 1920
    _, dq = FL.dequant_cost(2048, 3840, 8)
    assert dq == 2048 * 3840 + 2048 * 8 + 2048 * 3840 * 2
    # both kernels are bound by bytes on a v5e
    assert FL.least_time(flops, nbytes, PEAK) == nbytes / 819e9
