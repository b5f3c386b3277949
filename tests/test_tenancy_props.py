"""Hypothesis properties for multi-tenant admission scheduling.

Conservation across all three admission policies: for any tenant mix
(task counts, arrival processes, service times, weights), no task is
lost or duplicated, and per-tenant FIFO order is preserved — both in the
admission order and in the replayed per-tenant completion times.
(Module is collect-ignored by ``conftest.py`` when hypothesis is not
installed.)
"""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import sim
from repro.serving.tenancy import make_policy


@st.composite
def tenant_mixes(draw):
    n_hops = draw(st.integers(1, 3))
    n_tenants = draw(st.integers(1, 4))
    plans, arrivals = [], []
    for _ in range(n_tenants):
        n = draw(st.integers(0, 12))
        gaps = draw(st.lists(
            st.floats(0.0, 5e-3, allow_nan=False, allow_infinity=False),
            min_size=n, max_size=n))
        start = draw(st.floats(0.0, 10e-3))
        arr = list(start + np.cumsum([0.0] + gaps[:-1])) if n else []
        ps = []
        for i in range(n):
            comp = tuple(
                draw(st.floats(1e-4, 5e-3)) for _ in range(n_hops + 1))
            tx = tuple(draw(st.floats(0.0, 3e-3)) for _ in range(n_hops))
            # exit anywhere in the chain (post_init normalizes exit_hop
            # == n_hops back to a full run, and early_exit to exit_hop=0)
            ps.append(sim.SimPlan(
                compute=comp, tx=tx, early_exit=draw(st.booleans()),
                exit_hop=draw(st.one_of(st.none(),
                                        st.integers(0, n_hops)))))
        plans.append(ps)
        arrivals.append(arr)
    weights = [draw(st.floats(0.1, 8.0)) for _ in range(n_tenants)]
    return plans, arrivals, weights


@settings(max_examples=60, deadline=None)
@given(mix=tenant_mixes(), policy=st.sampled_from(["fifo", "rr", "wdrr"]))
def test_admission_order_conserves_tasks_and_fifo(mix, policy):
    plans, arrivals, weights = mix
    order, _ = sim.multitenant_admission(
        plans, arrivals, make_policy(policy, weights=weights))
    expected = {(t, i) for t in range(len(plans))
                for i in range(len(plans[t]))}
    # no task lost, none duplicated
    assert len(order) == len(expected)
    assert set(order) == expected
    # per-tenant FIFO preserved
    for t in range(len(plans)):
        idxs = [i for (tt, i) in order if tt == t]
        assert idxs == sorted(idxs)


@settings(max_examples=30, deadline=None)
@given(mix=tenant_mixes(), policy=st.sampled_from(["fifo", "rr", "wdrr"]))
def test_replayed_stream_conserves_per_tenant_completions(mix, policy):
    plans, arrivals, weights = mix
    if not any(plans):
        return  # nothing to replay
    mt = sim.simulate_multitenant_stream(
        plans, arrivals, make_policy(policy, weights=weights))
    assert len(mt.stream.done) == sum(len(p) for p in plans)
    for t in range(len(plans)):
        arr, done, exits = mt.tenant_view(t)
        assert len(done) == len(plans[t])
        # completions never precede arrivals + own end-segment compute
        for a, d, (i, p) in zip(arr, done, enumerate(plans[t])):
            assert d >= a + p.compute[0] - 1e-9
        # per-tenant full-pipeline completions are FIFO-ordered
        full = [d for d, e in zip(done, exits) if not e]
        assert all(d0 <= d1 + 1e-9 for d0, d1 in zip(full, full[1:]))


# ------------------------------------------------ micro-batching properties
@st.composite
def batched_mixes(draw):
    """A tenant mix plus per-tier batch caps, per-plan fixed launch
    fractions and optional staleness deadlines — the knobs of the greedy
    drain-up-to-cap-or-deadline batch formation rule."""
    plans, arrivals, weights = draw(tenant_mixes())
    n_hops = max((p.n_hops for ps in plans for p in ps), default=1)
    caps = [draw(st.integers(1, 4)) for _ in range(n_hops + 1)]
    for ps, arr in zip(plans, arrivals):
        for p, a in zip(ps, arr):
            frac = draw(st.floats(0.0, 1.0, allow_nan=False))
            p.t_fixed = tuple(c * frac for c in p.compute)
            if draw(st.booleans()):
                p.deadline = a + draw(st.floats(1e-3, 80e-3))
    return plans, arrivals, weights, caps


@settings(max_examples=40, deadline=None)
@given(mix=batched_mixes(), policy=st.sampled_from(["fifo", "rr", "wdrr"]))
def test_batched_multitenant_conserves_tasks_and_stream_order(mix, policy):
    """Whatever the caps, fixed fractions and deadlines: no task is lost
    or duplicated, batching never reorders completions within one
    tenant's stream (per exit tier), and every resource timeline stays
    sorted and disjoint."""
    plans, arrivals, weights, caps = mix
    if not any(plans):
        return
    mt = sim.simulate_multitenant_stream(
        plans, arrivals, make_policy(policy, weights=weights),
        batch_caps=caps)
    expected = {(t, i) for t in range(len(plans))
                for i in range(len(plans[t]))}
    assert len(mt.order) == len(expected)
    assert set(mt.order) == expected
    assert len(mt.stream.done) == len(expected)
    for t in range(len(plans)):
        _, done, _ = mt.tenant_view(t)
        by_tier = {}
        for d, eh in zip(done, mt.tenant_exit_hops(t)):
            by_tier.setdefault(eh, []).append(d)
        for ds in by_tier.values():
            assert all(d0 <= d1 + 1e-9 for d0, d1 in zip(ds, ds[1:]))
    for iv in (mt.stream.compute_intervals + mt.stream.link_intervals):
        assert sim._sorted_disjoint(iv)


@settings(max_examples=30, deadline=None)
@given(mix=batched_mixes(), policy=st.sampled_from(["fifo", "rr", "wdrr"]))
def test_cap_one_multitenant_is_decision_identical(mix, policy):
    """All-ones caps take the singleton path of every tier: admission
    order and timelines are *bitwise* equal to running without caps
    (policies are stateful, so each run gets a fresh instance)."""
    plans, arrivals, weights, caps = mix
    if not any(plans):
        return
    a = sim.simulate_multitenant_stream(
        plans, arrivals, make_policy(policy, weights=weights))
    b = sim.simulate_multitenant_stream(
        plans, arrivals, make_policy(policy, weights=weights),
        batch_caps=[1] * len(caps))
    assert a.order == b.order
    assert a.stream.done == b.stream.done
    assert a.stream.compute_intervals == b.stream.compute_intervals
    assert a.stream.link_intervals == b.stream.link_intervals


@st.composite
def batched_streams(draw):
    """A single admission-ordered stream with caps, fixed fractions and
    deadlines (tier-0 batching requires non-decreasing arrivals, which
    cumulative gaps give by construction)."""
    n_hops = draw(st.integers(1, 3))
    n = draw(st.integers(1, 20))
    gaps = draw(st.lists(
        st.floats(0.0, 5e-3, allow_nan=False, allow_infinity=False),
        min_size=n, max_size=n))
    arr = list(np.cumsum([0.0] + gaps[:-1]))
    plans = []
    for i in range(n):
        comp = tuple(
            draw(st.floats(1e-4, 5e-3)) for _ in range(n_hops + 1))
        frac = draw(st.floats(0.0, 1.0, allow_nan=False))
        dl = arr[i] + draw(st.floats(1e-3, 80e-3)) \
            if draw(st.booleans()) else None
        plans.append(sim.SimPlan(
            compute=comp, tx=tuple(draw(st.floats(0.0, 3e-3))
                                   for _ in range(n_hops)),
            early_exit=draw(st.booleans()),
            exit_hop=draw(st.one_of(st.none(), st.integers(0, n_hops))),
            t_fixed=tuple(c * frac for c in comp), deadline=dl))
    caps = [draw(st.integers(1, 4)) for _ in range(n_hops + 1)]
    return plans, arr, caps


@settings(max_examples=50, deadline=None)
@given(stream=batched_streams())
def test_batched_stream_conserves_orders_and_counts_batches(stream):
    """Single-stream form of the conservation/no-reordering property,
    plus the ``compute_batch_sizes`` bookkeeping: batch sizes respect
    the caps and jointly account for exactly the tasks that occupy each
    compute tier."""
    plans, arr, caps = stream
    res = sim.simulate_stream(plans, arr, batch_caps=caps)
    assert len(res.done) == len(plans)
    by_tier = {}
    for d, eh in zip(res.done, res.exit_hop):
        by_tier.setdefault(eh, []).append(d)
    for ds in by_tier.values():
        assert all(d0 <= d1 + 1e-9 for d0, d1 in zip(ds, ds[1:]))
    for iv in (res.compute_intervals + res.link_intervals):
        assert sim._sorted_disjoint(iv)
    if res.compute_batch_sizes:
        for k, (ivs, bs) in enumerate(zip(res.compute_intervals,
                                          res.compute_batch_sizes)):
            assert len(ivs) == len(bs)
            occ = sum(1 for eh in res.exit_hop
                      if sim.occupies_compute(eh, k))
            assert sum(bs) == occ
            assert all(1 <= b <= caps[k] for b in bs)
    # every link transfer stays per-task (links never batch)
    for k, ivs in enumerate(res.link_intervals):
        occ = sum(1 for eh in res.exit_hop if sim.occupies_link(eh, k))
        assert len(ivs) == occ
