"""Streaming serving loop: parity with answer_batch, drain/loss invariants,
typed backpressure, retrieval/decode overlap, and the real decode backend.

The tentpole contract: a drained StreamingEngine run over the paper
benchmark produces the same per-query records as one ``answer_batch`` call
over the arrival-ordered stream (chunking a stream through consecutive
``answer_batch`` calls never changes records — the consecutive-batches
parity the batched tests already pin). Property tests (hypothesis) fuzz
arrival traces; deterministic seeded variants pin the same invariants.
"""

import math

import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st

from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.serving.engine import QueueOverflowError, build_paper_engine
from repro.serving.generator import TransformerSlotDecoder
from repro.serving.scheduler import (
    ContinuousBatchScheduler,
    Rejection,
    Request,
    SchedulerConfig,
)
from repro.serving.streaming import StreamConfig, StreamingEngine, serve_stream
from repro.serving.workload import Arrival, ArrivalProcess, zipfian_indices

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)


def _sorted_rows(telemetry):
    return sorted(str(r.as_csv_row()) for r in telemetry.records)


# --------------------------------------------------------------------------- #
# Workloads                                                                    #
# --------------------------------------------------------------------------- #
def test_poisson_trace_deterministic_and_sorted():
    w1 = ArrivalProcess.poisson(QUERIES, REFS, rate_qps=50.0, seed=3)
    w2 = ArrivalProcess.poisson(QUERIES, REFS, rate_qps=50.0, seed=3)
    assert [a.time_s for a in w1] == [a.time_s for a in w2]
    times = [a.time_s for a in w1]
    assert times == sorted(times) and times[0] > 0
    assert w1.offered_qps == 50.0
    w3 = ArrivalProcess.poisson(QUERIES, REFS, rate_qps=50.0, seed=4)
    assert [a.time_s for a in w3] != times


def test_trace_validation():
    with pytest.raises(ValueError):
        ArrivalProcess.from_trace([0.0], QUERIES[:2])
    with pytest.raises(ValueError):
        ArrivalProcess.poisson(QUERIES[:2], REFS[:3], rate_qps=10.0)
    with pytest.raises(ValueError):
        ArrivalProcess.poisson(QUERIES[:2], rate_qps=0.0)
    with pytest.raises(ValueError):
        ArrivalProcess([Arrival(time_s=-1.0, query="q")])
    # unsorted trace input is sorted on construction
    w = ArrivalProcess.from_trace([0.5, 0.1], QUERIES[:2])
    assert [a.time_s for a in w] == [0.1, 0.5]


def test_zipfian_indices_deterministic_and_skewed():
    idx = zipfian_indices(20, 500, s=1.1, seed=3)
    assert idx.shape == (500,) and idx.min() >= 0 and idx.max() < 20
    np.testing.assert_array_equal(idx, zipfian_indices(20, 500, s=1.1, seed=3))
    assert not np.array_equal(idx, zipfian_indices(20, 500, s=1.1, seed=4))
    # rank-frequency skew: the head query strictly dominates the tail
    counts = np.bincount(idx, minlength=20)
    assert counts[0] > counts[-1]
    assert counts[0] > 500 / 20  # head above the uniform share
    # s=0 is uniform: skew strictly increases head mass
    flat = np.bincount(zipfian_indices(20, 500, s=0.0, seed=3), minlength=20)
    assert counts[0] > flat[0]
    assert zipfian_indices(5, 0).shape == (0,)


def test_zipfian_indices_validation():
    with pytest.raises(ValueError):
        zipfian_indices(0, 10)
    with pytest.raises(ValueError):
        zipfian_indices(5, -1)
    with pytest.raises(ValueError):
        zipfian_indices(5, 10, s=-0.5)


def test_zipfian_arrival_process_burst_and_poisson():
    w = ArrivalProcess.zipfian(QUERIES, REFS, length=50, s=1.2, seed=5)
    assert len(list(w)) == 50
    assert all(a.time_s == 0.0 for a in w)  # rate_qps=None → burst
    # repeats carry their query's own reference
    ref_of = dict(zip(QUERIES, REFS))
    assert all(a.reference == ref_of[a.query] for a in w)
    # same repeat sequence, Poisson-timed
    p = ArrivalProcess.zipfian(QUERIES, REFS, length=50, s=1.2, rate_qps=100.0, seed=5)
    assert [a.query for a in p] == [a.query for a in w]
    times = [a.time_s for a in p]
    assert times == sorted(times) and times[0] > 0
    with pytest.raises(ValueError):
        ArrivalProcess.zipfian(QUERIES[:3], REFS[:2], length=10)


def test_zipfian_stream_drives_cache_hits():
    """The realistic cache workload: a skewed repeat stream against a small
    LRU produces hits bounded away from both 0 and the degenerate 100%."""
    from repro.retrieval import CachedBackend

    eng = build_paper_engine(make_policy("router_default"))
    cached = CachedBackend(eng.backends["dense"], capacity=8)
    eng.backends["dense"] = cached
    streamer = StreamingEngine(eng, config=StreamConfig(overlap=False))
    result = streamer.run(ArrivalProcess.zipfian(QUERIES, REFS, length=60, s=1.3, seed=0))
    assert len(result.responses) == 60
    stats = cached.stats()
    assert stats.hits > 0  # the head queries repeat into the LRU
    assert stats.misses > 0  # cold start: every first occurrence misses
    assert stats.evictions > 0  # capacity 8 is far below the distinct keys


# --------------------------------------------------------------------------- #
# Parity: drained streaming run ≡ answer_batch                                 #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("overlap", [False, True])
def test_streaming_record_parity_with_answer_batch(overlap):
    ref = build_paper_engine(make_policy("router_default"))
    ref.answer_batch(QUERIES, REFS)

    eng = build_paper_engine(make_policy("router_default"))
    result = serve_stream(eng, QUERIES, REFS, config=StreamConfig(overlap=overlap))
    assert len(result.responses) == len(QUERIES)
    assert not result.rejections
    # order-normalized record parity — and in fact bit-identical CSV, since
    # micro-batches enter the engine in arrival order
    assert _sorted_rows(eng.telemetry) == _sorted_rows(ref.telemetry)
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()
    assert eng.ledger.total_billed == ref.ledger.total_billed


def test_streaming_parity_under_paced_arrivals_and_tiny_microbatches():
    """Chunk boundaries (arrival pacing × microbatch_max) never change records."""
    ref = build_paper_engine(make_policy("router_default"))
    ref.answer_batch(QUERIES, REFS)

    eng = build_paper_engine(make_policy("router_default"))
    workload = ArrivalProcess.poisson(QUERIES, REFS, rate_qps=2000.0, seed=11)
    streamer = StreamingEngine(eng, config=StreamConfig(overlap=True, microbatch_max=3))
    result = streamer.run(workload)
    assert len(result.responses) == len(QUERIES)
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()


def test_streaming_timings_populated_and_ordered():
    eng = build_paper_engine(make_policy("router_default"))
    result = serve_stream(eng, QUERIES, REFS, config=StreamConfig(overlap=False))
    assert len(result.timings) == len(QUERIES)
    for tm in result.timings.values():
        assert tm.routed_s is not None and tm.admitted_s is not None
        assert tm.first_token_s is not None and tm.last_token_s is not None
        assert tm.arrival_s <= tm.routed_s <= tm.last_token_s + 1e-9
        assert tm.first_token_s <= tm.last_token_s + 1e-9
        assert tm.ttft_s >= 0 and tm.ttlt_s >= tm.ttft_s - 1e-9
    s = result.summary()
    assert s["completed"] == len(QUERIES)
    assert s["p95_ttft_ms"] >= s["p50_ttft_ms"]
    assert s["p95_ttlt_ms"] >= s["p50_ttlt_ms"]
    assert math.isfinite(s["throughput_qps"])


# --------------------------------------------------------------------------- #
# Drain / no-loss invariants (shared checker; fuzzed + seeded variants)        #
# --------------------------------------------------------------------------- #
def _check_stream_invariants(times, n_queries, *, max_queue=1024, overlap=False,
                             microbatch_max=4):
    """Random arrival traces drain to completion: every arrival is either a
    response or a typed rejection, nothing is lost or double-decoded, and
    rejections only occur above the configured queue cap."""
    queries = [QUERIES[i % len(QUERIES)] for i in range(n_queries)]
    refs = [REFS[i % len(REFS)] for i in range(n_queries)]
    eng = build_paper_engine(make_policy("router_default"))
    sched = ContinuousBatchScheduler(
        SchedulerConfig(max_batch_slots=4, n_pages=512, page_size=16, max_queue=max_queue),
        catalog=eng.catalog,
    )
    streamer = StreamingEngine(
        eng, scheduler=sched,
        config=StreamConfig(overlap=overlap, microbatch_max=microbatch_max),
    )
    result = streamer.run(ArrivalProcess.from_trace(times, queries, refs))

    # conservation: every arrival routed exactly once or rejected at intake
    intake_rejects = [r for r in result.rejections if r.reason == "intake_full"]
    sched_rejects = [r for r in result.rejections if r.reason != "intake_full"]
    assert len(result.responses) + len(intake_rejects) == n_queries
    # every admitted request decoded to completion, none lost or duplicated
    assert len(sched.completed) == len(result.responses) - len(sched_rejects)
    done_ids = [r.request_id for r in sched.completed]
    assert len(done_ids) == len(set(done_ids))  # no double-decode
    for req in sched.completed:
        assert 1 <= req.generated <= req.max_new_tokens
        assert req.queue_wait is not None and req.queue_wait >= 0
    # all pages returned at drain
    assert sched.allocator.n_free == sched.config.n_pages
    # rejections only above the cap
    if max_queue >= n_queries and 1024 >= n_queries:
        assert not result.rejections
    for rej in sched_rejects:
        assert rej.reason in ("queue_full", "oversized")
        if rej.reason == "queue_full":
            assert rej.queue_depth >= max_queue
    return result


def test_stream_invariants_seeded_traces():
    rng = np.random.default_rng(0)
    for trial in range(4):
        n = int(rng.integers(1, 20))
        times = np.round(rng.uniform(0, 0.02, size=n), 6).tolist()
        _check_stream_invariants(times, n, overlap=bool(trial % 2),
                                 microbatch_max=int(rng.integers(1, 6)))


def test_stream_rejections_only_above_queue_cap():
    result = _check_stream_invariants([0.0] * 12, 12, max_queue=3, microbatch_max=12)
    rejects = [r for r in result.rejections if r.reason == "queue_full"]
    assert rejects, "expected queue_full rejections with max_queue=3"
    for rej in rejects:
        assert rej.queue_depth >= 3


@hypothesis.given(
    st.lists(st.floats(min_value=0.0, max_value=0.02), min_size=1, max_size=16),
    st.integers(min_value=1, max_value=6),  # microbatch size
    st.booleans(),  # overlap
)
@hypothesis.settings(max_examples=8, deadline=None)
def test_stream_invariants_random_traces(times, microbatch_max, overlap):
    _check_stream_invariants(times, len(times), overlap=overlap,
                             microbatch_max=microbatch_max)


@hypothesis.given(
    st.integers(min_value=1, max_value=12),  # arrivals
    st.integers(min_value=1, max_value=4),  # queue cap
)
@hypothesis.settings(max_examples=8, deadline=None)
def test_stream_rejections_bounded_by_cap(n, cap):
    result = _check_stream_invariants([0.0] * n, n, max_queue=cap,
                                      microbatch_max=n)
    for rej in result.rejections:
        if rej.reason == "queue_full":
            assert rej.queue_depth >= cap


# --------------------------------------------------------------------------- #
# Typed backpressure                                                           #
# --------------------------------------------------------------------------- #
def test_intake_cap_rejects_with_reason():
    eng = build_paper_engine(make_policy("router_default"))
    streamer = StreamingEngine(
        eng, config=StreamConfig(max_intake=4, microbatch_max=2, overlap=False)
    )
    result = streamer.run(ArrivalProcess.all_at_once(QUERIES[:12], REFS[:12]))
    # some arrivals must bounce off the 4-deep front door before the first
    # micro-batch drains it
    assert any(r.reason == "intake_full" for r in result.rejections)
    for rej in result.rejections:
        assert rej.queue_depth >= 4
        assert rej.request_id == -1  # never assigned an id: nothing leaked
    assert len(result.responses) + len(result.rejections) == 12


def test_serve_batch_overflow_carries_typed_rejections():
    eng = build_paper_engine(make_policy("router_default"))
    tiny = ContinuousBatchScheduler(SchedulerConfig(max_queue=3), catalog=eng.catalog)
    with pytest.raises(QueueOverflowError, match="accepted 3/28") as exc_info:
        eng.serve_batch(QUERIES, REFS, scheduler=tiny)
    rejections = exc_info.value.rejections
    assert len(rejections) == 25
    assert all(isinstance(r, Rejection) for r in rejections)
    assert all(r.reason == "queue_full" and r.queue_depth >= 3 for r in rejections)


def test_scheduler_try_submit_reasons():
    s = ContinuousBatchScheduler(SchedulerConfig(n_pages=4, page_size=16, max_queue=2))
    ok = Request(request_id=0, query="q", bundle_name="medium_rag",
                 prompt_tokens=10, max_new_tokens=2)
    assert s.try_submit(ok) is None
    oversized = Request(request_id=1, query="q", bundle_name="medium_rag",
                        prompt_tokens=70, max_new_tokens=10)
    rej = s.try_submit(oversized)
    assert rej is not None and rej.reason == "oversized"
    assert s.submit(Request(request_id=2, query="q", bundle_name="light_rag",
                            prompt_tokens=10, max_new_tokens=2))
    full = s.try_submit(Request(request_id=3, query="q", bundle_name="light_rag",
                                prompt_tokens=10, max_new_tokens=2))
    assert full is not None and full.reason == "queue_full" and full.queue_depth == 2
    assert [r.reason for r in s.rejections] == ["oversized", "queue_full"]
    # fresh-id watermark advances past REJECTED ids too: total_submitted is 2
    # here, but minting id 2 or 3 again would collide with live bookkeeping
    assert s.total_submitted == 2
    assert s.next_request_id == 4


# --------------------------------------------------------------------------- #
# Real decode backend on scheduler slots                                       #
# --------------------------------------------------------------------------- #
def test_slot_decoder_drives_streaming_run():
    eng = build_paper_engine(make_policy("router_default"))
    decoder = TransformerSlotDecoder.tiny(n_slots=4, max_len=256)
    sched = ContinuousBatchScheduler(
        SchedulerConfig(max_batch_slots=4, n_pages=1024, page_size=16),
        catalog=eng.catalog,
    )
    result = serve_stream(
        eng, QUERIES[:8], REFS[:8], decode_fn=decoder, scheduler=sched,
        config=StreamConfig(overlap=False),
    )
    assert len(sched.completed) == 8
    assert decoder.steps_run == len(result.step_history) > 0
    # slots released lazily at next call: an empty active set frees them all
    decoder(())
    assert not decoder.slot_of and len(decoder._free) == 4


def test_slot_decoder_slot_reuse_and_eos():
    decoder = TransformerSlotDecoder.tiny(n_slots=2, max_len=64)
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=2, n_pages=256))
    for i in range(5):
        s.submit(Request(request_id=i, query=f"q{i}", bundle_name="light_rag",
                         prompt_tokens=8, max_new_tokens=3))
    s.run_until_drained(decoder)
    assert len(s.completed) == 5  # 5 requests through 2 slots: reuse works
    assert all(r.generated <= 3 for r in s.completed)

    # EOS: with eos_id covering the whole vocab... instead pick the argmax
    # the model actually emits so the flag fires
    decoder2 = TransformerSlotDecoder.tiny(n_slots=1, max_len=64)
    probe = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=1, n_pages=64))
    probe.submit(Request(request_id=0, query="probe", bundle_name="light_rag",
                         prompt_tokens=4, max_new_tokens=1))
    probe.run_until_drained(decoder2)
    first_tok = int(np.asarray(decoder2.tokens)[0])
    decoder3 = TransformerSlotDecoder.tiny(n_slots=1, max_len=64, eos_id=first_tok)
    s3 = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=1, n_pages=64))
    s3.submit(Request(request_id=0, query="probe", bundle_name="light_rag",
                      prompt_tokens=4, max_new_tokens=100))
    s3.run_until_drained(decoder3)
    assert s3.completed[0].generated == 1  # model EOS beat the budget


def test_streaming_ids_fresh_after_scheduler_reuse_with_rejections():
    """Seeding ids from a reused scheduler must skip past rejected ids."""
    eng = build_paper_engine(make_policy("router_default"))
    sched = ContinuousBatchScheduler(
        SchedulerConfig(max_batch_slots=4, n_pages=512, page_size=16, max_queue=2),
        catalog=eng.catalog,
    )
    streamer = StreamingEngine(eng, scheduler=sched, config=StreamConfig(overlap=False))
    first = streamer.run(ArrivalProcess.all_at_once(QUERIES[:6], REFS[:6]))
    assert any(r.reason == "queue_full" for r in first.rejections)
    used = {req.request_id for req in sched.completed}
    streamer2 = StreamingEngine(eng, scheduler=sched, config=StreamConfig(overlap=False))
    second = streamer2.run(ArrivalProcess.all_at_once(QUERIES[6:8], REFS[6:8]))
    new = {req.request_id for req in sched.completed} - used
    assert len(second.responses) == 2
    assert not (new & used)  # no id reuse
    assert min(new) >= 6  # past every offered id from the first run


def test_slot_decoder_overflow_raises():
    decoder = TransformerSlotDecoder.tiny(n_slots=1, max_len=64)
    reqs = [Request(request_id=i, query=f"q{i}", bundle_name="light_rag",
                    prompt_tokens=4, max_new_tokens=2) for i in range(2)]
    with pytest.raises(RuntimeError, match="decoder slots"):
        decoder(reqs)


# --------------------------------------------------------------------------- #
# Scheduler regression: same-step multi-finish + queue_wait robustness         #
# --------------------------------------------------------------------------- #
def test_scheduler_same_step_multi_finish():
    """All active requests finishing on one step must retire cleanly (the
    finish loop iterates a snapshot, never the live dict)."""
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=8, n_pages=256))
    for i in range(8):
        s.submit(Request(request_id=i, query=f"q{i}", bundle_name="medium_rag",
                         prompt_tokens=8, max_new_tokens=5))
    m = s.step(lambda active: [True] * len(active))  # everyone EOS together
    assert m["finished"] == 8 and m["active"] == 0
    assert len(s.completed) == 8
    assert s.allocator.n_free == 256
    assert all(r.generated == 1 for r in s.completed)


def test_scheduler_decode_fn_length_mismatch_raises():
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=4, n_pages=256))
    for i in range(3):
        s.submit(Request(request_id=i, query=f"q{i}", bundle_name="light_rag",
                         prompt_tokens=8, max_new_tokens=2))
    with pytest.raises(ValueError, match="flags"):
        s.step(lambda active: [False])  # fewer flags than active requests


def test_queue_wait_same_tick_and_future_arrival():
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=2, n_pages=64))
    r0 = Request(request_id=0, query="q", bundle_name="light_rag",
                 prompt_tokens=8, max_new_tokens=1)
    s.submit(r0)
    s.step(lambda a: [False] * len(a))  # submit + admit on the same tick
    assert r0.queue_wait == 0
    # a caller-stamped arrival tick ahead of the scheduler clock (streaming
    # wall time vs step time skew) must clamp, not go negative
    r1 = Request(request_id=1, query="q", bundle_name="light_rag",
                 prompt_tokens=8, max_new_tokens=1, arrived_step=99)
    s.submit(r1)
    assert r1.arrived_step == 99  # submit preserves caller stamps
    s.run_until_drained(lambda a: [False] * len(a))
    assert r1.queue_wait == 0
    # unsubmitted request: no wait yet
    r2 = Request(request_id=2, query="q", bundle_name="light_rag",
                 prompt_tokens=8, max_new_tokens=1)
    assert r2.queue_wait is None


def test_telemetry_percentile():
    eng = build_paper_engine(make_policy("router_default"))
    eng.answer_batch(QUERIES[:8], REFS[:8])
    t = eng.telemetry
    p50, p95 = t.percentile("latency", [50, 95])
    assert p50 <= p95
    lats = sorted(r.latency for r in t.records)
    assert lats[0] <= p50 <= lats[-1]
    assert t.percentile("cost", 50) > 0
    empty = build_paper_engine(make_policy("router_default")).telemetry
    assert math.isnan(empty.percentile("latency", 50))
