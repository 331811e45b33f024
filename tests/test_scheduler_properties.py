"""Hypothesis property tests for the continuous-batching scheduler."""

import hypothesis
import hypothesis.strategies as st

from repro.serving.scheduler import ContinuousBatchScheduler, Request, SchedulerConfig

BUNDLES = ("direct_llm", "light_rag", "medium_rag", "heavy_rag")


@st.composite
def request_stream(draw):
    n = draw(st.integers(min_value=1, max_value=24))
    reqs = []
    for i in range(n):
        reqs.append(
            Request(
                request_id=i,
                query=f"q{i}",
                bundle_name=draw(st.sampled_from(BUNDLES)),
                prompt_tokens=draw(st.integers(min_value=1, max_value=120)),
                max_new_tokens=draw(st.integers(min_value=1, max_value=10)),
            )
        )
    return reqs


@hypothesis.given(
    request_stream(),
    st.integers(min_value=1, max_value=6),  # slots
    st.integers(min_value=16, max_value=128),  # pages
)
@hypothesis.settings(max_examples=40, deadline=None)
def test_scheduler_conservation_properties(reqs, slots, pages):
    """Invariants for any request stream / capacity:

    1. every admissible request completes (no loss, no duplication),
    2. pages are fully returned at drain (no leak),
    3. no request decodes past its budget,
    4. active slots never exceed capacity at any step.
    """
    cfg = SchedulerConfig(max_batch_slots=slots, n_pages=pages, page_size=16, max_queue=1024)
    s = ContinuousBatchScheduler(cfg)
    admissible = []
    for r in reqs:
        need = s._pages_needed(r)
        if need <= pages:  # requests larger than the whole pool can never run
            assert s.submit(r)
            admissible.append(r.request_id)
        # oversized requests would deadlock any scheduler; skip submitting

    max_active = 0
    for m in s.run_until_drained(lambda active: [False] * len(active), max_steps=5000):
        max_active = max(max_active, m["active"])

    done_ids = sorted(r.request_id for r in s.completed)
    assert done_ids == sorted(admissible)  # (1)
    assert s.allocator.n_free == pages  # (2)
    assert all(r.generated <= r.max_new_tokens for r in s.completed)  # (3)
    assert max_active <= slots  # (4)


@hypothesis.given(request_stream())
@hypothesis.settings(max_examples=20, deadline=None)
def test_fifo_within_bundle(reqs):
    """Within one bundle queue, admission order preserves arrival order."""
    s = ContinuousBatchScheduler(SchedulerConfig(max_batch_slots=2, n_pages=4096))
    for r in reqs:
        s.submit(r)
    s.run_until_drained(lambda active: [False] * len(active), max_steps=5000)
    by_bundle: dict[str, list[int]] = {}
    for r in sorted(s.completed, key=lambda r: (r.admitted_step, r.request_id)):
        by_bundle.setdefault(r.bundle_name, []).append(r.request_id)
    for ids in by_bundle.values():
        assert ids == sorted(ids)
