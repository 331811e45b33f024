"""Staged pipeline core: stage purity, N-deep/multi-worker parity, counters.

The tentpole contracts (serving/stages.py):

* The middle stages (retrieve/assemble/decode) are side-effect-free —
  calling one twice on the same artifact yields equal outputs and mutates
  no telemetry or billing state. That purity is what licenses running them
  on worker threads.
* A drained ``StreamingEngine`` run produces byte-identical Appendix-F CSVs
  to the sequential ``answer`` loop at every (pipeline_depth,
  retrieval_workers, overlap) setting — the finalize-stage replay absorbs
  any speculative staleness a deep pipeline introduces.
* The deterministic per-stage counters (``stage_batches``,
  ``retrieve_calls``) the CI gate reads from the burst-serial cell are
  bit-stable across runs.
"""

import dataclasses
import math
import time

import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st

from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.serving import stages
from repro.serving.engine import build_paper_engine
from repro.serving.generator import TransformerSlotDecoder
from repro.serving.scheduler import (
    ContinuousBatchScheduler,
    Request,
    SchedulerConfig,
)
from repro.serving.stages import StagePipeline
from repro.serving.streaming import StreamConfig, StreamingEngine, serve_stream
from repro.serving.workload import ArrivalProcess

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)

# Sequential reference, computed once per session (the `answer` loop is the
# auditable path every pipeline shape must reproduce byte-for-byte).
_REF: dict = {}


def _reference() -> tuple[str, int]:
    if not _REF:
        eng = build_paper_engine(make_policy("router_default"))
        for q, r in zip(QUERIES, REFS):
            eng.answer(q, reference=r)
        _REF["csv"] = eng.telemetry.to_csv()
        _REF["billed"] = eng.ledger.total_billed
    return _REF["csv"], _REF["billed"]


def _assert_parity(depth: int, workers: int, overlap: bool, microbatch: int) -> None:
    ref_csv, ref_billed = _reference()
    eng = build_paper_engine(make_policy("router_default"))
    result = serve_stream(
        eng,
        QUERIES,
        REFS,
        config=StreamConfig(
            overlap=overlap,
            pipeline_depth=depth,
            retrieval_workers=workers,
            microbatch_max=microbatch,
        ),
    )
    assert len(result.responses) == len(QUERIES)
    assert not result.rejections
    assert eng.telemetry.to_csv() == ref_csv
    assert eng.ledger.total_billed == ref_billed


# --------------------------------------------------------------------------- #
# Parity across the (depth, workers, overlap) grid                             #
# --------------------------------------------------------------------------- #
SWEEP = [
    (1, 1, False, 16),  # the old --no-overlap serial path (CI gate cell)
    (1, 2, True, 16),  # depth 1 forces serial even with workers configured
    (2, 1, True, 16),  # the old two-slot overlap, generalized
    (2, 2, True, 5),  # multi-worker retrieval with awkward chunking
    (4, 2, True, 3),  # deep pipeline: maximal speculative staleness
]


@pytest.mark.parametrize("depth,workers,overlap,microbatch", SWEEP)
def test_pipeline_parity_swept(depth, workers, overlap, microbatch):
    """Drained streaming ≡ sequential answer loop, byte-identical CSVs."""
    _assert_parity(depth, workers, overlap, microbatch)


@hypothesis.given(
    st.sampled_from([1, 2, 4]),  # pipeline_depth
    st.sampled_from([1, 2]),  # retrieval_workers
    st.booleans(),  # overlap
    st.sampled_from([3, 7, 16]),  # microbatch_max
)
@hypothesis.settings(max_examples=6, deadline=None)
def test_pipeline_parity_property(depth, workers, overlap, microbatch):
    _assert_parity(depth, workers, overlap, microbatch)


def test_deep_pipeline_parity_under_paced_arrivals():
    """Poisson pacing × tiny micro-batches × depth 4: chunk boundaries and
    in-flight depth never change records."""
    ref_csv, _ = _reference()
    eng = build_paper_engine(make_policy("router_default"))
    workload = ArrivalProcess.poisson(QUERIES, REFS, rate_qps=2000.0, seed=7)
    streamer = StreamingEngine(
        eng,
        config=StreamConfig(pipeline_depth=4, retrieval_workers=2, microbatch_max=3),
    )
    result = streamer.run(workload)
    assert len(result.responses) == len(QUERIES)
    assert eng.telemetry.to_csv() == ref_csv


# --------------------------------------------------------------------------- #
# Stage purity                                                                 #
# --------------------------------------------------------------------------- #
def _exec_key(ex) -> str:
    # NaN-tolerant structural equality (confidence is NaN for direct bundles)
    return str(dataclasses.asdict(ex))


def test_middle_stages_pure_and_side_effect_free():
    """retrieve/assemble/decode twice on the same artifact: equal outputs,
    zero telemetry/billing/counter mutation. finalize commits exactly once."""
    eng = build_paper_engine(make_policy("router_default"))
    n = 12
    routed = stages.route(eng, QUERIES[:n], REFS[:n])
    records_before = len(eng.telemetry.records)
    bills_before = len(eng.ledger.bills)
    counter_before = eng._query_counter
    stats_before = {k: str(v) for k, v in eng.telemetry.stats.items()}

    r1 = stages.retrieve(eng, routed)
    r2 = stages.retrieve(eng, routed)
    assert r1.search_calls == r2.search_calls > 0
    assert set(r1.retrievals) == set(r2.retrievals)
    for i in r1.retrievals:
        np.testing.assert_array_equal(r1.retrievals[i][0], r2.retrievals[i][0])
        np.testing.assert_array_equal(r1.retrievals[i][1], r2.retrievals[i][1])

    a1 = stages.assemble(eng, r1)
    a2 = stages.assemble(eng, r1)
    assert a1.final_bundle == a2.final_bundle
    assert a1.passages == a2.passages
    assert a1.prompts == a2.prompts
    assert a1.embedded == a2.embedded
    assert [str(c) for c in a1.confidences] == [str(c) for c in a2.confidences]

    d1 = stages.decode(eng, a1)
    d2 = stages.decode(eng, a1)
    assert [_exec_key(e) for e in d1.executions] == [_exec_key(e) for e in d2.executions]

    # the middle stages mutated no shared engine state
    assert len(eng.telemetry.records) == records_before
    assert len(eng.ledger.bills) == bills_before
    assert eng._query_counter == counter_before
    assert {k: str(v) for k, v in eng.telemetry.stats.items()} == stats_before

    # finalize is the commit point: telemetry + ledger advance exactly here
    responses = stages.finalize(eng, d1)
    assert len(responses) == n
    assert len(eng.telemetry.records) == records_before + n
    assert len(eng.ledger.bills) == bills_before + n


def test_failed_batch_returns_query_ids():
    """A batch that dies before committing must hand back its query ids —
    latency noise is seeded per qid, so a leak would shift every later
    record off the reference stream."""
    eng = build_paper_engine(make_policy("router_default"))
    real_generator = eng.generator

    class Boom:
        def generate(self, *a, **k):
            raise RuntimeError("boom")

    eng.generator = Boom()
    with pytest.raises(RuntimeError, match="boom"):
        eng.answer_batch(QUERIES[:4], REFS[:4])
    assert eng._query_counter == 0
    assert not eng.telemetry.records and not eng.ledger.bills
    # a failure inside route itself (before ids are allocated) leaks nothing
    real_embedder = eng.embedder

    class BoomEmbed:
        dim = real_embedder.dim

        def embed(self, texts):
            raise RuntimeError("embed boom")

    eng.embedder = BoomEmbed()
    with pytest.raises(RuntimeError, match="embed boom"):
        eng.answer_batch(QUERIES[:4], REFS[:4])
    assert eng._query_counter == 0
    eng.embedder = real_embedder
    # after recovery the engine reproduces the reference stream exactly
    eng.generator = real_generator
    for q, r in zip(QUERIES, REFS):
        eng.answer(q, reference=r)
    assert eng.telemetry.to_csv() == _reference()[0]


def test_answer_batch_is_stage_composition():
    """The explicit 5-stage chain reproduces answer_batch bit-for-bit."""
    a = build_paper_engine(make_policy("router_default"))
    a.answer_batch(QUERIES[:8], REFS[:8])
    b = build_paper_engine(make_policy("router_default"))
    routed = stages.route(b, QUERIES[:8], REFS[:8])
    decoded = stages.decode(b, stages.assemble(b, stages.retrieve(b, routed)))
    stages.finalize(b, decoded)
    assert a.telemetry.to_csv() == b.telemetry.to_csv()


# --------------------------------------------------------------------------- #
# StagePipeline executor                                                       #
# --------------------------------------------------------------------------- #
def test_pipeline_depth_and_order():
    """Submission-order recombination: responses come back in submit order
    even when later micro-batches finish their middle stages first."""
    eng = build_paper_engine(make_policy("router_default"))
    pipe = StagePipeline(eng, depth=4, workers=2)
    try:
        for s in range(0, 12, 3):
            pipe.submit(QUERIES[s : s + 3], REFS[s : s + 3], tag=s)
        assert not pipe.can_submit()
        with pytest.raises(RuntimeError, match="pipeline full"):
            pipe.submit(QUERIES[12:13], REFS[12:13])
        tags = []
        while pipe.in_flight:
            pipe.wait_head(5.0)
            done = pipe.poll()
            assert done is not None
            tag, responses = done
            tags.append(tag)
            assert [r.record.query for r in responses] == QUERIES[tag : tag + 3]
    finally:
        pipe.shutdown()
    assert tags == [0, 3, 6, 9]
    assert pipe.stage_batches == 4
    # finalize ran in arrival order → records are the arrival-ordered stream
    assert [r.query for r in eng.telemetry.records] == QUERIES[:12]


def test_stage_counters_deterministic_and_reported():
    """The burst-serial cell's per-stage counters are bit-stable run to run —
    the property the CI gate (gate.stage_batches / gate.retrieve_calls)
    relies on."""

    def run_once():
        eng = build_paper_engine(make_policy("router_default"))
        return serve_stream(eng, QUERIES, REFS, config=StreamConfig(overlap=False))

    r1, r2 = run_once(), run_once()
    assert r1.stage_batches == r2.stage_batches == math.ceil(len(QUERIES) / 16)
    assert r1.retrieve_calls == r2.retrieve_calls > 0
    s = r1.summary()
    assert s["stage_batches"] == r1.stage_batches
    assert s["retrieve_calls"] == r1.retrieve_calls
    assert s["pipeline_depth"] == 1 and s["overlap"] is False


# --------------------------------------------------------------------------- #
# Satellite: single record→Request conversion                                  #
# --------------------------------------------------------------------------- #
def test_scheduler_make_requests_mints_fresh_ids():
    eng = build_paper_engine(make_policy("fixed_direct"))
    responses = eng.answer_batch(QUERIES[:4])
    records = [r.record for r in responses]
    sched = ContinuousBatchScheduler(catalog=eng.catalog)
    reqs1 = sched.make_requests(records)
    assert [r.request_id for r in reqs1] == [0, 1, 2, 3]
    # watermark advances at mint time: a second batch can never collide even
    # if the first was never submitted (e.g. rejected wholesale upstream)
    reqs2 = sched.make_requests(records)
    assert [r.request_id for r in reqs2] == [4, 5, 6, 7]
    assert all(r.bundle_name == "direct_llm" for r in reqs1)
    assert all(r.max_new_tokens >= 1 for r in reqs1)


# --------------------------------------------------------------------------- #
# Satellite: paced decode                                                      #
# --------------------------------------------------------------------------- #
def _drain_two_requests(decoder):
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=2, n_pages=64))
    for i in range(2):
        s.submit(Request(request_id=i, query=f"q{i}", bundle_name="light_rag",
                         prompt_tokens=4, max_new_tokens=5))
    decoder.warmup()  # compile outside the timed window
    t0 = time.perf_counter()
    s.run_until_drained(decoder)
    return s, time.perf_counter() - t0


def test_paced_decode_rate_floor_and_unchanged_results():
    free, _ = _drain_two_requests(TransformerSlotDecoder.tiny(n_slots=2, max_len=64))
    paced_dec = TransformerSlotDecoder.tiny(n_slots=2, max_len=64, tokens_per_s=100.0)
    paced, t_paced = _drain_two_requests(paced_dec)
    # pacing only inserts waits: identical step count and per-request tokens
    assert paced.step_count == free.step_count == 5
    assert [r.generated for r in paced.completed] == [r.generated for r in free.completed]
    # 5 steps at 100 tok/s → at least 4 full 10ms inter-step gaps
    assert t_paced >= (paced.step_count - 1) / 100.0 - 1e-3
    # reset() restarts the pacing clock (no carried-over deadline)
    paced_dec.reset()
    assert paced_dec._next_step_t == 0.0


def test_paced_decode_validation_and_default_off():
    with pytest.raises(ValueError, match="tokens_per_s"):
        TransformerSlotDecoder.tiny(n_slots=1, max_len=64, tokens_per_s=0.0)
    dec = TransformerSlotDecoder.tiny(n_slots=1, max_len=64)
    assert dec.tokens_per_s is None
