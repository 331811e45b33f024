"""Property tests for arrival workloads (zipfian_indices / ArrivalProcess).

Hypothesis fuzzes the contracts; the fixed-seed example tests pin them at
known points.
"""

import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro.serving.workload import ArrivalProcess, zipfian_indices

given = hypothesis.given
settings = hypothesis.settings


# -- zipfian_indices ---------------------------------------------------------


@given(
    n_items=st.integers(min_value=1, max_value=200),
    length=st.integers(min_value=0, max_value=500),
    s=st.floats(min_value=0.0, max_value=3.0, allow_nan=False),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=50, deadline=None)
def test_zipf_seed_determinism_and_range(n_items, length, s, seed):
    a = zipfian_indices(n_items, length, s=s, seed=seed)
    b = zipfian_indices(n_items, length, s=s, seed=seed)
    assert np.array_equal(a, b)
    assert a.shape == (length,)
    if length:
        assert a.min() >= 0 and a.max() < n_items


def test_zipf_rank_frequency_monotone_fixed_seed():
    # Seeded draw => deterministic counts; with s=1.2 over 16 ranks and 4096
    # draws, the empirical head-to-tail ordering of the first few ranks is a
    # fixed property of this exact sample, not a statistical assertion.
    idx = zipfian_indices(16, 4096, s=1.2, seed=0)
    counts = np.bincount(idx, minlength=16)
    assert counts[0] > counts[1] > counts[2]
    assert counts[0] > counts[-1]
    # aggregate monotonicity: the head half strictly outweighs the tail half
    assert counts[:8].sum() > counts[8:].sum()


@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_zipf_head_outweighs_tail(seed):
    # With s >= 1 over 32 ranks and 1024 draws the head half carries >2/3 of
    # the ideal mass; the sample margin is astronomically safe for any seed.
    idx = zipfian_indices(32, 1024, s=1.1, seed=seed)
    counts = np.bincount(idx, minlength=32)
    assert counts[:16].sum() > counts[16:].sum()


def test_zipf_validation():
    with pytest.raises(ValueError):
        zipfian_indices(0, 5)
    with pytest.raises(ValueError):
        zipfian_indices(5, -1)
    with pytest.raises(ValueError):
        zipfian_indices(5, 5, s=-0.1)


# -- ArrivalProcess invariants ----------------------------------------------


def queries_of(n):
    return [f"query {i}" for i in range(n)]


@given(
    n=st.integers(min_value=1, max_value=64),
    rate=st.floats(min_value=0.5, max_value=500.0, allow_nan=False),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=50, deadline=None)
def test_poisson_monotone_and_offered(n, rate, seed):
    p = ArrivalProcess.poisson(queries_of(n), rate_qps=rate, seed=seed)
    times = [a.time_s for a in p]
    assert all(t >= 0 for t in times)
    assert times == sorted(times)
    assert p.offered_qps == rate
    assert p.makespan_s == times[-1]
    q = ArrivalProcess.poisson(queries_of(n), rate_qps=rate, seed=seed)
    assert [a.time_s for a in q] == times  # seed determinism


@given(
    n=st.integers(min_value=1, max_value=64),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=50, deadline=None)
def test_from_trace_round_trip(n, seed):
    rng = np.random.default_rng(seed)
    times = sorted(float(t) for t in rng.uniform(0, 10, size=n))
    qs = queries_of(n)
    p = ArrivalProcess.from_trace(times, qs)
    assert [a.time_s for a in p] == times
    assert [a.query for a in p] == qs
    # default offered load = count / span (inf when the span is 0)
    span = times[-1]
    if span > 0:
        assert p.offered_qps == pytest.approx(n / span)
    assert p.makespan_s == times[-1]


def test_default_offered_qps_consistency():
    p = ArrivalProcess.from_trace([0.0, 1.0, 2.0, 4.0], queries_of(4))
    assert p.offered_qps == pytest.approx(4 / 4.0)
    burst = ArrivalProcess.all_at_once(queries_of(3))
    assert burst.offered_qps == float("inf")
    assert burst.makespan_s == 0.0
    assert len(ArrivalProcess([])) == 0


def test_negative_times_rejected():
    with pytest.raises(ValueError):
        ArrivalProcess.from_trace([-1.0, 0.0], queries_of(2))


@given(
    n=st.integers(min_value=2, max_value=32),
    length=st.integers(min_value=1, max_value=128),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=50, deadline=None)
def test_zipfian_stream_reference_alignment(n, length, seed):
    qs = queries_of(n)
    refs = [f"answer {i}" for i in range(n)]
    p = ArrivalProcess.zipfian(qs, refs, length=length, s=1.1, seed=seed)
    assert len(p) == length
    lookup = dict(zip(qs, refs))
    for a in p:
        assert a.reference == lookup[a.query]  # each repeat keeps its reference


@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_diurnal_and_bursty_monotone(seed):
    d = ArrivalProcess.diurnal(
        queries_of(48), length=48, base_qps=5.0, peak_qps=50.0,
        period_s=2.0, seed=seed,
    )
    b = ArrivalProcess.bursty(
        queries_of(48), length=48, base_qps=5.0, burst_qps=200.0,
        phase_s=0.5, seed=seed,
    )
    for p in (d, b):
        times = [a.time_s for a in p]
        assert len(times) == 48
        assert all(t >= 0 for t in times)
        assert times == sorted(times)
    # seed determinism
    d2 = ArrivalProcess.diurnal(
        queries_of(48), length=48, base_qps=5.0, peak_qps=50.0,
        period_s=2.0, seed=seed,
    )
    assert [a.time_s for a in d2] == [a.time_s for a in d]


def test_diurnal_validation():
    with pytest.raises(ValueError):
        ArrivalProcess.diurnal(queries_of(4), length=4, base_qps=0.0, peak_qps=10.0)
    with pytest.raises(ValueError):
        ArrivalProcess.diurnal(queries_of(4), length=8, base_qps=1.0, peak_qps=10.0)
    with pytest.raises(ValueError):
        ArrivalProcess.bursty(queries_of(4), length=4, base_qps=1.0, burst_qps=10.0,
                              phase_s=0.0)


def test_merge_stable_order_and_tenants():
    # same-timestamp arrivals keep the order of `processes` (sorted is
    # stable) — the deterministic tie-break multi-tenant admission relies on
    a = ArrivalProcess.all_at_once(["a0", "a1"], tenant="a")
    b = ArrivalProcess.all_at_once(["b0"], tenant="b")
    m = ArrivalProcess.merge([a, b])
    assert [x.query for x in m] == ["a0", "a1", "b0"]
    assert [x.tenant for x in m] == ["a", "a", "b"]
    assert m.offered_qps == float("inf")
    # interleaving by time across tenants
    x = ArrivalProcess.from_trace([0.0, 2.0], ["x0", "x1"], tenant="x")
    y = ArrivalProcess.from_trace([1.0, 3.0], ["y0", "y1"], tenant="y")
    m2 = ArrivalProcess.merge([x, y])
    assert [q.query for q in m2] == ["x0", "y0", "x1", "y1"]
    assert m2.offered_qps == pytest.approx(x.offered_qps + y.offered_qps)
    assert len(ArrivalProcess.merge([])) == 0


def test_tenant_stamping_constructors():
    p = ArrivalProcess.poisson(queries_of(3), rate_qps=10.0, tenant="t1")
    z = ArrivalProcess.zipfian(queries_of(3), length=9, tenant="t2")
    assert all(a.tenant == "t1" for a in p)
    assert all(a.tenant == "t2" for a in z)
    assert all(a.tenant is None for a in ArrivalProcess.all_at_once(queries_of(2)))
