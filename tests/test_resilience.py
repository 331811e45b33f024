"""Resilient serving: fault injection, retries, breakers, degradation ladder.

Pins the PR's contracts:

1. **Deterministic chaos** — a :class:`FaultyBackend` draws every fault
   decision from ``(seed, call_index)``, so a profile is a *schedule*:
   identical wrappers produce identical failures, stalls, and degraded
   payloads, run after run.
2. **Bounded, seeded resilience** — retries never exceed the policy bound,
   backoff sequences are reproducible under a fixed seed, and the circuit
   breaker's closed/open/half-open machine honours cooldown and probe
   quotas (hypothesis-fuzzed, plus deterministic cases).
3. **Zero-fault parity** — wrapping healthy backends in the full
   fault+cache+resilience decorator stack changes nothing: byte-identical
   telemetry CSVs on the paper and extended catalogs, bit-identical drained
   streaming vs ``answer_batch`` across (depth, workers, shards).
4. **Graceful degradation** — when a backend is truly down, the catalog-
   derived ladder answers every query (down to retrieval-free direct
   inference), tags the records ``degraded``, and keeps forced answers out
   of the EMA priors and recall calibration.

The canonical end-to-end chaos scenarios (real stalls, wall-clock
timeouts) live in tests/test_resilience_chaos.py behind ``-m chaos``;
everything here uses injectable clocks/sleeps and stays tier-1 fast.
"""

import math

import numpy as np
import pytest

import jax.numpy as jnp

import hypothesis
import hypothesis.strategies as st

from repro.core.bundles import make_catalog
from repro.core.policies import make_policy
from repro.core.telemetry import CSV_FIELDS, QueryRecord, TelemetryStore
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.retrieval import (
    DenseBackend,
    DenseIndex,
    FaultProfile,
    FaultyBackend,
    TransientBackendError,
    has_injected_faults,
    scale_backends,
    wrap_cached,
    wrap_faulty,
)
from repro.retrieval.chunking import Passage
from repro.serving.resilience import (
    BackendUnavailableError,
    BreakerConfig,
    CircuitBreaker,
    ResilienceConfig,
    ResilientBackend,
    RetryPolicy,
    backoff_delays_ms,
    degradation_ladder,
    wrap_resilient,
)
from repro.serving.scheduler import Request
from repro.serving.stages import StageError, StagePipeline
from repro.serving.streaming import StreamConfig, serve_stream
from repro.serving.engine import build_paper_engine

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)


def _corpus(n: int = 37, d: int = 32, seed: int = 0) -> DenseIndex:
    rng = np.random.default_rng(seed)
    emb = rng.normal(size=(n, d)).astype(np.float32)
    passages = [Passage(i, f"passage {i}") for i in range(n)]
    return DenseIndex(jnp.asarray(emb), passages)


def _queries(nq: int = 4, d: int = 32, seed: int = 1) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))


class FakeClock:
    """Manually-advanced monotonic clock for breaker/deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# --------------------------------------------------------------------------- #
# 1. FaultProfile + FaultyBackend determinism                                  #
# --------------------------------------------------------------------------- #
def test_fault_profile_validation_and_parse():
    with pytest.raises(ValueError):
        FaultProfile(failure_rate=1.5)
    with pytest.raises(ValueError):
        FaultProfile(stall_every=-1)
    assert FaultProfile().is_zero
    assert not FaultProfile(failure_rate=0.1).is_zero

    name, p = FaultProfile.parse("dense:failure_rate=0.3,stall_every=6,stall_ms=1500,seed=2")
    assert name == "dense"
    assert (p.failure_rate, p.stall_every, p.stall_ms, p.seed) == (0.3, 6, 1500.0, 2)
    assert isinstance(p.stall_every, int) and isinstance(p.seed, int)

    with pytest.raises(ValueError):
        FaultProfile.parse("no-colon-spec")
    with pytest.raises(ValueError):
        FaultProfile.parse("dense:bogus_field=1")


def test_faulty_backend_schedule_deterministic():
    """Two wrappers over the same profile raise on the same call indices."""
    profile = FaultProfile(failure_rate=0.4, seed=5)

    def schedule() -> list[bool]:
        fb = FaultyBackend(DenseBackend(_corpus()), profile)
        out = []
        for _ in range(40):
            try:
                fb.search_batch(None, _queries(2), 5)
                out.append(False)
            except TransientBackendError:
                out.append(True)
        return out

    a, b = schedule(), schedule()
    assert a == b
    assert any(a) and not all(a)  # schedule actually mixes outcomes


def test_faulty_backend_zero_profile_is_transparent():
    idx = _corpus()
    inner = DenseBackend(idx)
    fb = FaultyBackend(inner, FaultProfile())
    q = _queries(3)
    ref_s, ref_i = inner.search_batch(None, q, 7)
    s, i = fb.search_batch(None, q, 7)
    assert np.array_equal(np.asarray(s), np.asarray(ref_s))
    assert np.array_equal(np.asarray(i), np.asarray(ref_i))
    assert fb.injected == {
        "failures": 0, "spikes": 0, "stalls": 0, "empties": 0, "truncations": 0,
    }
    # protocol surface delegates
    assert fb.name == inner.name and fb.size == idx.size
    assert has_injected_faults(fb)
    assert not has_injected_faults(inner)


def test_faulty_backend_stall_schedule_periodic():
    slept: list[float] = []
    fb = FaultyBackend(
        DenseBackend(_corpus()),
        FaultProfile(stall_every=3, stall_ms=1000.0, seed=0),
        sleep=slept.append,
    )
    for _ in range(9):
        fb.search_batch(None, _queries(1), 4)
    # calls 2, 5, 8 (0-based; (idx+1) % 3 == 0) stall
    assert fb.injected["stalls"] == 3
    assert slept == [1.0, 1.0, 1.0]


def test_faulty_backend_degraded_payloads():
    fb_empty = FaultyBackend(DenseBackend(_corpus()), FaultProfile(empty_rate=1.0))
    s, i = fb_empty.search_batch(None, _queries(3), 6)
    assert s.shape == (3, 0) and i.shape == (3, 0)
    assert fb_empty.injected["empties"] == 1

    fb_trunc = FaultyBackend(DenseBackend(_corpus()), FaultProfile(truncate_rate=1.0))
    s, i = fb_trunc.search_batch(None, _queries(3), 6)
    assert s.shape == (3, 3) and i.shape == (3, 3)  # ceil(6/2)
    assert fb_trunc.injected["truncations"] == 1


def test_wrap_faulty_unknown_backend_raises():
    backends = {"dense": DenseBackend(_corpus())}
    with pytest.raises(ValueError, match="unknown backends"):
        wrap_faulty(backends, {"bm25": FaultProfile(failure_rate=1.0)})
    wrapped = wrap_faulty(backends, {"dense": FaultProfile(failure_rate=1.0)})
    assert isinstance(wrapped["dense"], FaultyBackend)


# --------------------------------------------------------------------------- #
# 2. Backoff + retry bounds                                                    #
# --------------------------------------------------------------------------- #
def test_backoff_deterministic_and_bounded():
    a = backoff_delays_ms(6, base_ms=2.0, multiplier=2.0, max_ms=20.0, jitter=0.5, seed=3)
    b = backoff_delays_ms(6, base_ms=2.0, multiplier=2.0, max_ms=20.0, jitter=0.5, seed=3)
    assert a == b and len(a) == 6
    c = backoff_delays_ms(6, base_ms=2.0, multiplier=2.0, max_ms=20.0, jitter=0.5, seed=4)
    assert a != c  # the seed is the schedule
    for i, d in enumerate(a):
        cap = min(2.0 * 2.0**i, 20.0)
        assert 0.5 * cap <= d <= cap  # jitter only shrinks, never exceeds cap
    assert backoff_delays_ms(0) == []


def test_retry_policy_seeds_per_call():
    pol = RetryPolicy(max_retries=3, seed=9)
    assert pol.delays_ms(0) == pol.delays_ms(0)
    assert pol.delays_ms(0) != pol.delays_ms(1)  # decorrelated across calls
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=2.0)


class AlwaysFailBackend:
    """Minimal protocol stub that raises a transient fault on every search."""

    name = "dense"
    requires_query_vecs = True

    def __init__(self, inner):
        self.inner = inner
        self.attempts = 0

    @property
    def cost(self):
        return self.inner.cost

    @property
    def size(self):
        return self.inner.size

    def get_passages(self, ids):
        return self.inner.get_passages(ids)

    def search_batch(self, queries, query_vecs, k):
        self.attempts += 1
        raise TransientBackendError("down")


def test_resilient_backend_retry_bound_and_events():
    inner = AlwaysFailBackend(DenseBackend(_corpus()))
    slept: list[float] = []
    rb = ResilientBackend(
        inner,
        ResilienceConfig(retry=RetryPolicy(max_retries=2, seed=7)),
        sleep=slept.append,
    )
    with pytest.raises(BackendUnavailableError) as exc:
        rb.search_batch_resilient(None, _queries(1), 3)
    assert inner.attempts == 3  # 1 + max_retries, never more
    ev = exc.value.events
    assert ev.failures == 3 and ev.retries == 2 and ev.timeouts == 0
    # the observed backoff sleeps are exactly the policy's seeded sequence
    expected = [d / 1000.0 for d in RetryPolicy(max_retries=2, seed=7).delays_ms(0)]
    assert slept == pytest.approx(expected)


def test_resilient_backend_zero_fault_passthrough():
    idx = _corpus()
    inner = DenseBackend(idx)
    rb = ResilientBackend(inner, ResilienceConfig())
    q = _queries(4)
    ref_s, ref_i = inner.search_batch(None, q, 8)
    s, i, ev, cache = rb.search_batch_resilient(None, q, 8)
    assert np.array_equal(s, np.asarray(ref_s)) and np.array_equal(i, np.asarray(ref_i))
    assert not ev.any and cache == {}
    assert rb.name == "dense" and rb.size == idx.size


def test_resilient_backend_timeout_counts_and_recovers():
    class SlowOnceBackend(AlwaysFailBackend):
        def search_batch(self, queries, query_vecs, k):
            self.attempts += 1
            if self.attempts == 1:
                import time as _t

                _t.sleep(0.25)
            return self.inner.search_batch(queries, query_vecs, k)

    inner = SlowOnceBackend(DenseBackend(_corpus()))
    # warm the dense-search jit closure for this (shape, k) outside the timed
    # path: on a cold/loaded host the first compile alone can blow the 40 ms
    # budget, turning every retry into a timeout and flaking the test
    inner.inner.search_batch(None, _queries(1), 3)
    rb = ResilientBackend(
        inner,
        ResilienceConfig(timeout_ms=40.0, retry=RetryPolicy(max_retries=2, backoff_base_ms=0.0, jitter=0.0)),
    )
    try:
        s, i, ev, _ = rb.search_batch_resilient(None, _queries(1), 3)
        assert ev.timeouts == 1 and ev.retries >= 1
        assert s.shape[0] == 1
    finally:
        rb.shutdown()


def test_resilient_backend_short_circuits_when_open():
    inner = AlwaysFailBackend(DenseBackend(_corpus()))
    clock = FakeClock()
    rb = ResilientBackend(
        inner,
        ResilienceConfig(
            retry=RetryPolicy(max_retries=0),
            breaker=BreakerConfig(failure_threshold=1, cooldown_s=60.0),
        ),
        clock=clock,
        sleep=lambda _s: None,
    )
    with pytest.raises(BackendUnavailableError):
        rb.search_batch_resilient(None, _queries(1), 3)
    assert inner.attempts == 1 and rb.breaker.state == "open"
    with pytest.raises(BackendUnavailableError) as exc:
        rb.search_batch_resilient(None, _queries(1), 3)
    assert inner.attempts == 1  # open breaker: the inner backend never ran
    assert exc.value.events.short_circuits == 1


# --------------------------------------------------------------------------- #
# 3. Circuit-breaker state machine                                             #
# --------------------------------------------------------------------------- #
def test_breaker_opens_after_threshold_and_cooldown_half_opens():
    clock = FakeClock()
    br = CircuitBreaker(BreakerConfig(failure_threshold=3, cooldown_s=10.0), clock=clock)
    assert br.state == "closed"
    assert not br.record_failure() and not br.record_failure()
    assert br.state == "closed"
    assert br.record_failure()  # third consecutive failure opens
    assert br.state == "open" and br.opens == 1
    assert not br.allow()
    clock.advance(9.99)
    assert not br.allow()  # still cooling down
    clock.advance(0.02)
    assert br.state == "half_open"
    assert br.allow()  # the probe slot
    assert not br.allow()  # quota is one concurrent probe
    br.record_success()
    assert br.state == "closed" and br.allow()


def test_breaker_failed_probe_reopens_with_fresh_cooldown():
    clock = FakeClock()
    br = CircuitBreaker(BreakerConfig(failure_threshold=1, cooldown_s=5.0), clock=clock)
    br.record_failure()
    assert br.state == "open"
    clock.advance(5.0)
    assert br.allow()  # half-open probe
    assert br.record_failure()  # failed probe re-opens immediately
    assert br.state == "open" and br.opens == 2
    clock.advance(4.9)
    assert not br.allow()  # the cooldown restarted at the re-open
    clock.advance(0.2)
    assert br.allow()
    br.record_success()
    assert br.state == "closed"


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(BreakerConfig(failure_threshold=2, cooldown_s=1.0), clock=FakeClock())
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"  # interleaved success broke the streak
    br.record_failure()
    assert br.state == "open"


@hypothesis.given(
    st.lists(
        st.one_of(
            st.just(("fail",)),
            st.just(("ok",)),
            st.tuples(st.just("wait"), st.floats(min_value=0.0, max_value=30.0)),
        ),
        max_size=60,
    ),
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=1, max_value=3),
)
@hypothesis.settings(max_examples=60, deadline=None)
def test_breaker_invariants_under_arbitrary_event_sequences(events, threshold, probes):
    """Safety properties for any interleaving of outcomes and clock advances:
    an open breaker never admits before its cooldown; half-open admits at
    most ``probes`` concurrent probes; ``opens`` only ever increments."""
    clock = FakeClock()
    cooldown = 10.0
    br = CircuitBreaker(
        BreakerConfig(failure_threshold=threshold, cooldown_s=cooldown, half_open_probes=probes),
        clock=clock,
    )
    opened_at = None
    prev_opens = 0
    for ev in events:
        if ev[0] == "wait":
            clock.advance(ev[1])
            continue
        admitted = br.allow()
        if opened_at is not None and clock() - opened_at < cooldown:
            assert not admitted, "open breaker admitted before cooldown"
        if not admitted:
            continue
        if ev[0] == "fail":
            br.record_failure()
        else:
            br.record_success()
        assert br.opens >= prev_opens
        prev_opens = br.opens
        opened_at = clock() if br.state == "open" else None
    # half-open probe quota: after a full cooldown, exactly `probes` admits
    if br.state == "open":
        clock.advance(cooldown + 1.0)
        assert sum(br.allow() for _ in range(probes + 5)) == probes


@hypothesis.given(st.integers(min_value=0, max_value=6), st.integers(min_value=0, max_value=2**31 - 1))
@hypothesis.settings(max_examples=60, deadline=None)
def test_backoff_property_deterministic_and_capped(n, seed):
    a = backoff_delays_ms(n, base_ms=1.0, multiplier=3.0, max_ms=9.0, jitter=0.4, seed=seed)
    assert a == backoff_delays_ms(n, base_ms=1.0, multiplier=3.0, max_ms=9.0, jitter=0.4, seed=seed)
    assert len(a) == n
    assert all(0.0 <= d <= 9.0 for d in a)


# --------------------------------------------------------------------------- #
# 4. Degradation ladder                                                        #
# --------------------------------------------------------------------------- #
def test_ladder_paper_catalog_orders_shallower_then_direct():
    cat = make_catalog("paper")
    names = {b.name: i for i, b in enumerate(cat)}
    ladder = [cat[i].name for i in degradation_ladder(cat, names["heavy_rag"])]
    assert ladder == ["medium_rag", "light_rag", "direct_llm"]
    assert [cat[i].name for i in degradation_ladder(cat, names["light_rag"])] == ["direct_llm"]
    assert degradation_ladder(cat, names["direct_llm"]) == []


def test_ladder_extended_catalog_ends_direct_and_never_deepens():
    cat = make_catalog("extended")
    for idx, b in enumerate(cat):
        rungs = degradation_ladder(cat, idx)
        if b.skip_retrieval:
            assert rungs == []
            continue
        assert cat[rungs[-1]].skip_retrieval  # always lands on direct inference
        for r in rungs:
            cand = cat[r]
            # a rung never asks the same struggling backend for MORE work
            if cand.backend == b.backend and not cand.skip_retrieval:
                assert cand.top_k < b.top_k


# --------------------------------------------------------------------------- #
# 5. Zero-fault parity                                                         #
# --------------------------------------------------------------------------- #
def _resilient_stack(eng, *, shards: int = 1, cache: int = 0):
    """The full CLI decorator stack with a zero fault profile everywhere."""
    from repro.retrieval import BackendStackConfig, build_backend_stack

    eng.backends = build_backend_stack(
        eng.backends,
        BackendStackConfig(
            shards=shards,
            cache_size=cache,
            fault_profiles={name: FaultProfile() for name in eng.backends},
            resilience=ResilienceConfig(),
        ),
        index=eng.index,
    )
    return eng


@pytest.mark.parametrize("preset", ["paper", "extended"])
def test_zero_fault_stack_csv_parity(preset):
    catalog = make_catalog(preset)
    ref = build_paper_engine(make_policy("router_default", catalog=catalog))
    ref.answer_batch(QUERIES, REFS)
    ref.answer_batch(QUERIES, REFS)

    eng = _resilient_stack(
        build_paper_engine(make_policy("router_default", catalog=catalog)), cache=32
    )
    eng.answer_batch(QUERIES, REFS)
    eng.answer_batch(QUERIES, REFS)

    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()  # byte-identical
    assert not any(r.degraded for r in eng.telemetry.records)


@pytest.mark.parametrize(
    "depth,workers,shards", [(1, 1, 1), (2, 2, 1), (2, 1, 3), (4, 2, 3)]
)
def test_zero_fault_streaming_parity_sweep(depth, workers, shards):
    """Drained streaming through the zero-fault resilient stack stays
    bit-identical to one answer_batch call at every pipeline shape."""
    ref = build_paper_engine(make_policy("router_default"))
    ref.answer_batch(QUERIES, REFS)

    eng = _resilient_stack(
        build_paper_engine(make_policy("router_default")), shards=shards
    )
    result = serve_stream(
        eng, QUERIES, REFS,
        config=StreamConfig(pipeline_depth=depth, retrieval_workers=workers),
    )
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()
    s = result.summary()
    assert s["completed"] == len(QUERIES) and s["rejected"] == 0
    res = s["resilience"]
    assert res["degraded"] == 0 and res["breaker_opens"] == 0
    assert res["breaker_state"] == {name: "closed" for name in eng.backends}
    assert res["stalled_workers"] == []


def test_degraded_fields_not_in_csv_schema():
    assert "degraded" not in CSV_FIELDS and "fallback_depth" not in CSV_FIELDS
    rec = QueryRecord(
        query="q", strategy="direct_llm", bundle="direct_llm", utility=0.0,
        quality_proxy=0.5, realized_utility=0.0, latency=1.0, prompt_tokens=1,
        completion_tokens=1, embedding_tokens=0, retrieval_confidence=float("nan"),
        complexity_score=0.0, degraded=True, fallback_depth=3,
    )
    assert set(rec.as_csv_row()) == set(CSV_FIELDS)


# --------------------------------------------------------------------------- #
# 6. Degraded answers: tagging, EMA exclusion, calibration exclusion           #
# --------------------------------------------------------------------------- #
def _dead_dense_engine():
    """Paper engine whose dense backend always fails, resilience-wrapped with
    zero retries and an instant breaker — every retrieval bundle degrades."""
    eng = build_paper_engine(make_policy("router_default"))
    eng.backends["dense"] = FaultyBackend(
        eng.backends["dense"], FaultProfile(failure_rate=1.0, seed=0)
    )
    eng.backends = wrap_resilient(
        eng.backends,
        ResilienceConfig(
            retry=RetryPolicy(max_retries=0),
            breaker=BreakerConfig(failure_threshold=1, cooldown_s=1e9),
        ),
        sleep=lambda _s: None,
    )
    return eng


def test_degraded_answers_tagged_and_complete():
    eng = _dead_dense_engine()
    responses = eng.answer_batch(QUERIES, REFS)
    assert len(responses) == len(QUERIES)  # every query still answered
    degraded = [r.record for r in responses if r.record.degraded]
    assert degraded  # the workload routes through retrieval bundles
    assert all(r.bundle == "direct_llm" for r in degraded)  # ladder terminal
    assert all(r.fallback_depth >= 1 for r in degraded)
    healthy = [r.record for r in responses if not r.record.degraded]
    assert all(r.fallback_depth == 0 for r in healthy)


def test_degraded_records_excluded_from_ema_priors():
    cat = make_catalog("paper")
    store = TelemetryStore(cat)
    kw = dict(
        query="q", utility=0.0, quality_proxy=0.9, realized_utility=0.0,
        latency=100.0, prompt_tokens=10, completion_tokens=5, embedding_tokens=0,
        retrieval_confidence=0.5, complexity_score=0.1,
    )
    store.log(QueryRecord(strategy="direct_llm", bundle="direct_llm", degraded=True,
                          fallback_depth=2, **kw))
    assert len(store.records) == 1  # stays auditable in the record stream
    assert store.stats["direct_llm"].count == 0  # but never refines priors
    store.log(QueryRecord(strategy="direct_llm", bundle="direct_llm", **kw))
    assert store.stats["direct_llm"].count == 1


def test_calibration_refuses_fault_injecting_backends():
    catalog = make_catalog("extended")
    eng = build_paper_engine(make_policy("router_default", catalog=catalog))
    eng.backends["bm25"] = FaultyBackend(
        eng.backends["bm25"], FaultProfile(empty_rate=1.0)
    )
    measured = eng.calibrate_backend_recall(QUERIES[:4], backends=["bm25", "ivf"])
    assert math.isnan(measured["bm25"])  # fabricated rows never observed
    assert math.isfinite(measured["ivf"])
    assert "bm25" not in eng.telemetry.recall_obs
    assert eng.telemetry.recall_obs["ivf"].count == 4


def test_calibration_refuses_unavailable_backends():
    catalog = make_catalog("extended")
    eng = build_paper_engine(make_policy("router_default", catalog=catalog))
    inner = eng.backends["ivf"]

    class DownBackend:
        name = inner.name
        cost = inner.cost
        requires_query_vecs = inner.requires_query_vecs
        size = inner.size
        get_passages = staticmethod(inner.get_passages)

        def search_batch(self, queries, query_vecs, k):
            raise TransientBackendError("down")

    eng.backends["ivf"] = ResilientBackend(
        DownBackend(),
        ResilienceConfig(retry=RetryPolicy(max_retries=0)),
        sleep=lambda _s: None,
    )
    measured = eng.calibrate_backend_recall(QUERIES[:3], backends=["ivf"])
    assert math.isnan(measured["ivf"])
    assert "ivf" not in eng.telemetry.recall_obs


# --------------------------------------------------------------------------- #
# 7. Per-request deadlines                                                     #
# --------------------------------------------------------------------------- #
def test_scheduler_rejects_expired_deadline():
    from repro.serving.scheduler import ContinuousBatchScheduler

    sched = ContinuousBatchScheduler()
    late = Request(request_id=0, query="q", bundle_name="direct_llm",
                   prompt_tokens=4, max_new_tokens=4, deadline_ms=10.0, age_ms=11.0)
    rej = sched.try_submit(late)
    assert rej is not None and rej.reason == "deadline_exceeded"
    assert sched.rejections[-1].reason == "deadline_exceeded"

    ok = Request(request_id=1, query="q", bundle_name="direct_llm",
                 prompt_tokens=4, max_new_tokens=4, deadline_ms=10.0, age_ms=9.0)
    assert sched.try_submit(ok) is None
    # no deadline → no check, even with a stamped age
    unset = Request(request_id=2, query="q", bundle_name="direct_llm",
                    prompt_tokens=4, max_new_tokens=4, age_ms=1e9)
    assert sched.try_submit(unset) is None


def test_streaming_generous_deadline_rejects_nothing():
    eng = build_paper_engine(make_policy("router_default"))
    result = serve_stream(
        eng, QUERIES[:8], REFS[:8],
        config=StreamConfig(pipeline_depth=1, request_deadline_ms=60_000.0),
    )
    assert result.summary()["completed"] == 8
    assert result.summary()["rejected"] == 0


# --------------------------------------------------------------------------- #
# 8. StagePipeline: typed worker errors + heartbeat stalls                     #
# --------------------------------------------------------------------------- #
class BuggyBackend:
    """A backend with a programming error — NOT a RetrievalFault, so the
    retrieve stage must propagate it typed, never walk the ladder."""

    name = "dense"
    requires_query_vecs = True

    def __init__(self, inner):
        self.inner = inner

    @property
    def cost(self):
        return self.inner.cost

    @property
    def size(self):
        return self.inner.size

    def get_passages(self, ids):
        return self.inner.get_passages(ids)

    def search_batch(self, queries, query_vecs, k):
        raise ValueError("boom: not a fault, a bug")


@pytest.mark.parametrize("depth", [1, 2])
def test_pipeline_worker_exception_is_typed_with_batch_identity(depth):
    eng = build_paper_engine(make_policy("router_default"))
    eng.backends["dense"] = BuggyBackend(eng.backends["dense"])
    pipeline = StagePipeline(eng, depth=depth, workers=1)
    try:
        with pytest.raises(StageError) as exc:
            pipeline.submit(QUERIES[:4], REFS[:4], tag=None)
            # at depth > 1 the failure surfaces at the poll that harvests it
            while pipeline.poll() is not None or pipeline.in_flight:
                pass
        err = exc.value
        assert err.batch_index == 0 and err.qid0 == 0 and err.n == 4
        assert isinstance(err.__cause__, ValueError)
        assert "micro-batch 0" in str(err)
    finally:
        pipeline.shutdown()


def test_pipeline_heartbeat_reports_stalled_busy_worker():
    clock = FakeClock()
    eng = build_paper_engine(make_policy("router_default"))
    pipeline = StagePipeline(eng, depth=1, workers=1, worker_timeout_s=5.0, clock=clock)
    try:
        assert pipeline.stalled_workers() == []
        # simulate a worker mid-batch: last beat at t=0, batch in hand
        pipeline.heartbeats.beat("worker-test")
        pipeline._busy["worker-test"] = 0
        clock.advance(4.0)
        assert pipeline.stalled_workers() == []  # within deadline
        clock.advance(2.0)
        assert pipeline.stalled_workers() == ["worker-test"]  # wedged
        pipeline._busy.pop("worker-test")
        assert pipeline.stalled_workers() == []  # idle workers never report
    finally:
        pipeline.shutdown()


def test_streaming_summary_surfaces_resilience_schema():
    eng = build_paper_engine(make_policy("router_default"))
    result = serve_stream(eng, QUERIES[:4], REFS[:4], config=StreamConfig(pipeline_depth=1))
    res = result.summary()["resilience"]
    for key in ("retries", "timeouts", "failures", "short_circuits", "breaker_opens",
                "fallbacks", "degraded", "fallback_depth_total",
                "breaker_state", "stalled_workers"):
        assert key in res
    assert res["breaker_state"] == {}  # no resilient wrapper in this run
