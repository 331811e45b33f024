"""Cached + sharded retrieval backends and the recall-calibration loop.

Pins the PR's three contracts:

1. **Cache transparency** — a :class:`CachedBackend` is result-identical to
   its inner backend across arbitrary hit/miss/eviction sequences
   (hypothesis-fuzzed + deterministic variants), and its counters are
   deterministic on serial runs.
2. **Shard exactness** — a :class:`ShardedBackend` merge equals the
   unsharded top-k bit-for-bit, including non-divisible shard sizes,
   ``k`` greater than a shard (or the whole corpus), and score ties across
   shard boundaries; drained serving runs with caching + sharding enabled
   are bit-identical to the plain engine at every
   (pipeline_depth, retrieval_workers, shards) setting.
3. **Calibration shrinkage** — measured ``recall_vs_exact`` observations
   refine routing's recall priors only after the min-sample threshold, and
   dense bundles keep their exact static identity throughout.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import hypothesis
import hypothesis.strategies as st

from repro.core.bundles import Bundle, BundleCatalog, make_catalog
from repro.core.guardrails import GuardrailConfig, Guardrails
from repro.core.policies import make_policy
from repro.core.router import Router
from repro.core.telemetry import TelemetryStore
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.retrieval import (
    CachedBackend,
    DenseBackend,
    DenseIndex,
    ShardedBackend,
    shard_bounds,
    wrap_cached,
)
from repro.retrieval.chunking import Passage
from repro.serving.engine import build_paper_engine
from repro.serving.streaming import StreamConfig, serve_stream

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)


def _corpus(n: int = 37, d: int = 32, seed: int = 0) -> DenseIndex:
    rng = np.random.default_rng(seed)
    emb = rng.normal(size=(n, d)).astype(np.float32)
    if n > 12:
        emb[n - 1] = emb[2]  # exact duplicates → score ties across shards
        emb[n - 5] = emb[11]
    passages = [Passage(i, f"passage {i}") for i in range(n)]
    return DenseIndex(jnp.asarray(emb), passages)


def _queries(nq: int = 5, d: int = 32, seed: int = 1) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))


# --------------------------------------------------------------------------- #
# 1. Cache semantics                                                           #
# --------------------------------------------------------------------------- #
def test_cached_backend_result_identical_and_counts():
    idx = _corpus()
    inner = DenseBackend(idx)
    cached = CachedBackend(inner, capacity=3)
    q = _queries(5)

    ref_s, ref_i = inner.search_batch(None, q, 10)
    s1, i1, d1 = cached.search_batch_stats(None, q, 10)
    assert np.array_equal(s1, np.asarray(ref_s))
    assert np.array_equal(i1, np.asarray(ref_i))
    assert (d1.hits, d1.misses) == (0, 5)
    assert d1.evictions == 2  # 5 inserts through a 3-slot LRU

    # the 3 most recent rows hit; the 2 evicted ones miss again
    s2, i2, d2 = cached.search_batch_stats(None, q, 10)
    assert np.array_equal(s2, s1) and np.array_equal(i2, i1)
    assert d2.hits + d2.misses == 5
    assert cached.stats().hits == d1.hits + d2.hits

    # a different k is a different key space
    s3, _, d3 = cached.search_batch_stats(None, q, 4)
    assert np.array_equal(s3, np.asarray(inner.search_batch(None, q, 4)[0]))
    assert d3.hits == 0

    assert len(cached) <= cached.capacity
    assert cached.name == "dense" and cached.size == idx.size


def test_cached_backend_counters_deterministic_across_runs():
    runs = []
    for _ in range(2):
        cached = CachedBackend(DenseBackend(_corpus()), capacity=10)
        deltas = []
        for seed in (1, 2, 1, 3, 2, 1):
            _, _, d = cached.search_batch_stats(None, _queries(4, seed=seed), 8)
            deltas.append((d.hits, d.misses, d.evictions))
        runs.append(deltas)
    assert runs[0] == runs[1]
    assert any(h for h, _, _ in runs[0])  # repeats actually hit


def test_cached_backend_validation():
    inner = DenseBackend(_corpus())
    with pytest.raises(ValueError):
        CachedBackend(inner, capacity=0)
    with pytest.raises(ValueError):
        CachedBackend(inner, capacity=2).search_batch(["q"], None, 3)


def test_cached_hybrid_keys_on_text_and_forwards_none_loudly():
    """Hybrid reads BOTH the vectors and the query text (BM25 half): the
    cache key must cover the text, and a ``queries=None`` call must fail as
    loudly wrapped as unwrapped — never silently score substituted ''."""
    eng = build_paper_engine(
        make_policy("router_default", catalog=make_catalog("extended"))
    )
    hybrid = eng.backends["hybrid"]
    cached = CachedBackend(hybrid, capacity=16)
    qs = QUERIES[:4]
    vecs = jnp.asarray(np.asarray(eng.embedder.embed(qs), np.float32))
    ref = hybrid.search_batch(qs, vecs, 8)
    for _ in range(2):  # second pass = pure cache hits
        got = cached.search_batch(qs, vecs, 8)
        assert np.array_equal(got[0], np.asarray(ref[0]))
        assert np.array_equal(got[1], np.asarray(ref[1]))
    assert cached.stats().hits == 4
    # same vectors, different text → different key, and the BM25 half sees
    # the new text (no stale fused rows served)
    other = ["completely different lexical content"] * 4
    got2 = cached.search_batch(other, vecs, 8)
    ref2 = hybrid.search_batch(other, vecs, 8)
    assert np.array_equal(got2[0], np.asarray(ref2[0]))
    assert cached.stats().misses == 8
    # None queries: the inner hybrid raises; the wrapper must not mask it
    with pytest.raises(Exception):
        hybrid.search_batch(None, vecs, 8)
    with pytest.raises(Exception):
        cached.search_batch(None, vecs, 8)


@hypothesis.given(
    st.lists(st.tuples(st.integers(0, 7), st.integers(1, 12)), min_size=1, max_size=30),
    st.integers(1, 6),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_cache_identity_property(seq, capacity):
    """Any (query, k) request sequence through any capacity is
    result-identical to the uncached backend (hit/miss/eviction agnostic)."""
    idx = _corpus(n=17, d=16)
    inner = DenseBackend(idx)
    cached = CachedBackend(inner, capacity=capacity)
    pool = np.asarray(_queries(8, d=16, seed=9))
    for qi, k in seq:
        q = jnp.asarray(pool[qi : qi + 1])
        ref = inner.search_batch(None, q, k)
        got = cached.search_batch(None, q, k)
        assert np.array_equal(got[0], np.asarray(ref[0]))
        assert np.array_equal(got[1], np.asarray(ref[1]))
    st_ = cached.stats()
    assert st_.hits + st_.misses == len(seq)
    assert len(cached) <= capacity


# --------------------------------------------------------------------------- #
# 2. Shard exactness                                                           #
# --------------------------------------------------------------------------- #
def test_shard_bounds_cover_and_validate():
    assert shard_bounds(10, 3) == [(0, 4), (4, 7), (7, 10)]
    assert shard_bounds(6, 6) == [(i, i + 1) for i in range(6)]
    with pytest.raises(ValueError):
        shard_bounds(3, 4)
    with pytest.raises(ValueError):
        shard_bounds(3, 0)


@pytest.mark.parametrize("n_shards", [1, 2, 3, 5])
@pytest.mark.parametrize("k", [1, 5, 13, 20, 50])
def test_sharded_equals_unsharded_bitwise(n_shards, k):
    """Sharded merge == unsharded top-k: non-divisible shard sizes (37/3),
    k > shard rows (13), k > corpus (50), and tie rows across shards."""
    idx = _corpus()
    plain = DenseBackend(idx)
    sharded = ShardedBackend.from_dense(idx, n_shards=n_shards)
    q = _queries(5)
    ps, pi = plain.search_batch(None, q, k)
    ss, si = sharded.search_batch(None, q, k)
    assert np.array_equal(np.asarray(ps), ss)
    assert np.array_equal(np.asarray(pi), si)


def test_sharded_threaded_and_passages():
    idx = _corpus()
    sharded = ShardedBackend.from_dense(idx, n_shards=3, workers=3)
    try:
        plain = DenseBackend(idx)
        q = _queries(6)
        ps, pi = plain.search_batch(None, q, 7)
        ss, si = sharded.search_batch(None, q, 7)
        assert np.array_equal(np.asarray(ps), ss)
        assert np.array_equal(np.asarray(pi), si)
        # global-id passage fetch crosses shard boundaries
        texts = [p.text for p in sharded.get_passages([0, 13, 36, 5])]
        assert texts == ["passage 0", "passage 13", "passage 36", "passage 5"]
    finally:
        sharded.shutdown()


def test_sharded_validation():
    idx = _corpus(n=9)
    b = DenseBackend(idx)
    with pytest.raises(ValueError):
        ShardedBackend([], [])
    with pytest.raises(ValueError):
        ShardedBackend([b, b], [0])
    with pytest.raises(ValueError):
        ShardedBackend([b, b], [5, 0])


@hypothesis.given(
    st.integers(5, 40), st.integers(1, 5), st.integers(1, 50), st.integers(0, 1000)
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_sharded_identity_property(n, n_shards, k, seed):
    """Random corpus sizes × shard counts × depths: bit-identical merge."""
    hypothesis.assume(n_shards <= n)
    idx = _corpus(n=n, d=16, seed=seed)
    plain = DenseBackend(idx)
    sharded = ShardedBackend.from_dense(idx, n_shards=n_shards)
    q = _queries(3, d=16, seed=seed + 1)
    ps, pi = plain.search_batch(None, q, k)
    ss, si = sharded.search_batch(None, q, k)
    assert np.array_equal(np.asarray(ps), ss)
    assert np.array_equal(np.asarray(pi), si)


# --------------------------------------------------------------------------- #
# Serving parity with caching + sharding enabled                               #
# --------------------------------------------------------------------------- #
def test_paper_engine_parity_cached_sharded_batched():
    """answer_batch with a cached, 3-way-sharded dense backend is
    byte-identical to the plain paper engine over two epochs."""
    ref = build_paper_engine(make_policy("router_default"))
    ref.answer_batch(QUERIES, REFS)
    ref.answer_batch(QUERIES, REFS)

    eng = build_paper_engine(make_policy("router_default"))
    eng.backends["dense"] = CachedBackend(
        ShardedBackend.from_dense(eng.index, n_shards=3), capacity=64
    )
    eng.answer_batch(QUERIES, REFS)
    eng.answer_batch(QUERIES, REFS)
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()
    assert eng.ledger.total_billed == ref.ledger.total_billed
    stats = eng.backends["dense"].stats()
    assert stats.hits > 0  # epoch 2 reuses epoch-1 rows


@pytest.mark.parametrize("depth,workers", [(1, 1), (2, 1), (2, 2), (4, 1), (4, 2)])
@pytest.mark.parametrize("shards", [1, 3])
def test_streaming_parity_cached_sharded_sweep(depth, workers, shards):
    """Drained streaming ≡ answer_batch with caching + sharding at every
    (pipeline_depth, retrieval_workers, shards) setting (acceptance sweep;
    workers > 1 is meaningless at depth 1, so (1, 2) is the one omitted
    grid point)."""
    ref = build_paper_engine(make_policy("router_default"))
    ref.answer_batch(QUERIES, REFS)

    eng = build_paper_engine(make_policy("router_default"))
    if shards > 1:
        eng.backends["dense"] = ShardedBackend.from_dense(eng.index, n_shards=shards)
    eng.backends = wrap_cached(eng.backends, capacity=64)
    result = serve_stream(
        eng,
        QUERIES,
        REFS,
        config=StreamConfig(pipeline_depth=depth, retrieval_workers=workers),
    )
    assert len(result.responses) == len(QUERIES)
    assert not result.rejections
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()
    cache = result.summary()["backend_cache"]
    assert "dense" in cache and cache["dense"]["misses"] > 0


def test_extended_catalog_parity_with_cache_wrap():
    """Wrapping every backend of the *extended* catalog must not move a
    record. Regression test: `CachedBackend.__len__` made an empty cache
    falsy, so an `if backend` truthiness check in the engine's structural
    latency predictions silently dropped non-dense latency scales to 1.0
    and shifted routing (invisible on the paper catalog, whose only scale
    IS 1.0)."""
    catalog = make_catalog("extended")
    ref = build_paper_engine(make_policy("router_default", catalog=catalog))
    ref.answer_batch(QUERIES, REFS)

    eng = build_paper_engine(make_policy("router_default", catalog=catalog))
    eng.backends = wrap_cached(eng.backends, capacity=64)
    assert eng.backends["dense"] and bool(eng.backends["bm25"])  # truthy when empty
    # rebuild priors the way a pre-construction wrap would see them
    lat, cost = eng._structural_predictions()
    np.testing.assert_array_equal(lat, ref._structural_predictions()[0])
    np.testing.assert_array_equal(cost, ref._structural_predictions()[1])
    eng.answer_batch(QUERIES, REFS)
    assert eng.telemetry.to_csv() == ref.telemetry.to_csv()


def test_streaming_cache_counters_deterministic_on_serial_path():
    def run():
        eng = build_paper_engine(make_policy("router_default"))
        eng.backends = wrap_cached(eng.backends, capacity=32)
        res = serve_stream(eng, QUERIES, REFS, config=StreamConfig(overlap=False))
        return res.summary()["backend_cache"]

    assert run() == run()


# --------------------------------------------------------------------------- #
# 3. Recall-prior calibration                                                  #
# --------------------------------------------------------------------------- #
def _two_bundle_catalog() -> BundleCatalog:
    """dense vs ivf at the same depth/priors: only the recall prior (and the
    backend latency scale) discriminates them. Statically, ivf's latency
    edge (0.55 scale) wins the deep band."""
    return BundleCatalog(
        (
            Bundle("direct_llm", 0, True, 0.52, 8.0, 190.0, depth_affinity=-1.0),
            Bundle("dense_mid", 5, False, 0.74, 60.0, 275.0, depth_affinity=0.6),
            Bundle(
                "ivf_mid", 5, False, 0.74, 60.0, 275.0,
                depth_affinity=0.6, backend="ivf",
            ),
        )
    )


def test_observe_recall_validation_and_threshold():
    t = TelemetryStore(make_catalog("extended"), recall_min_samples=4)
    with pytest.raises(ValueError):
        t.observe_recall("ivf", 1.5)
    assert t.refined_recall_priors() is None
    for _ in range(3):
        t.observe_recall("ivf", 0.95)
    # below min samples: still the static curve (None = fast path)
    assert t.refined_recall_priors() is None
    t.observe_recall("ivf", 0.95)
    refined = t.refined_recall_priors()
    assert refined is not None
    names = t.catalog.names
    ivf_i = names.index("ivf_medium")
    static = t.catalog["ivf_medium"].backend_cost.recall_prior
    # shrinkage: strictly between static curve and observed mean
    assert static < refined[ivf_i] < 0.95
    # every dense bundle keeps the exact static identity
    for i, n in enumerate(names):
        if t.catalog[n].backend == "dense":
            assert refined[i] == 1.0


def test_clone_for_replay_carries_recall_observations():
    t = TelemetryStore(make_catalog("extended"), recall_min_samples=2)
    for _ in range(4):
        t.observe_recall("ivf", 0.5)
    clone = t.clone_for_replay()
    np.testing.assert_array_equal(
        clone.refined_recall_priors(), t.refined_recall_priors()
    )
    clone.observe_recall("ivf", 0.9)
    assert t.recall_obs["ivf"].count == 4  # isolation


def test_refined_recall_shifts_routing_only_after_enough_samples():
    catalog = _two_bundle_catalog()
    router = Router(catalog)
    store = TelemetryStore(catalog, recall_min_samples=5)
    cplx = np.asarray([0.5])

    # static curve: ivf's latency edge beats dense at its assumed 0.81 recall
    choice0, _ = router.route_batch_np(cplx)
    assert catalog.names[int(choice0[0])] == "ivf_mid"

    # a few terrible recall measurements: below the min-sample threshold
    # the shrinkage guard keeps the static curve — routing must not move
    for _ in range(4):
        store.observe_recall("ivf", 0.2)
    assert store.refined_recall_priors() is None

    # enough observations: the refined prior exposes the recall miss and
    # routing escalates to the exact dense bundle
    for _ in range(26):
        store.observe_recall("ivf", 0.2)
    refined = store.refined_recall_priors()
    ivf_i = catalog.index_of("ivf_mid")
    assert 0.2 < refined[ivf_i] < 0.81  # shrinkage, not a snap to the mean
    choice1, _ = router.route_batch_np(
        cplx, recall_override=refined.astype(np.float32)
    )
    assert catalog.names[int(choice1[0])] == "dense_mid"


def test_calibrate_backend_recall_engine_loop():
    eng = build_paper_engine(
        make_policy("router_default", catalog=make_catalog("extended"))
    )
    eng.telemetry.recall_min_samples = 5
    assert eng._priors()[2] is None
    measured = eng.calibrate_backend_recall(QUERIES[:8])
    assert set(measured) == {"bm25", "ivf", "hybrid"}
    assert all(0.0 <= v <= 1.0 for v in measured.values())
    recall = eng._priors()[2]
    assert recall is not None
    names = eng.catalog.names
    assert recall[names.index("heavy_rag")] == np.float32(1.0)  # dense identity
    with pytest.raises(ValueError):
        eng.calibrate_backend_recall([])
    with pytest.raises(ValueError):
        eng.calibrate_backend_recall(QUERIES[:2], backends=["nope"])


def test_paper_catalog_routing_unchanged_without_observations():
    """The calibration seam is invisible until observations exist: the
    paper engine's records stay byte-identical to a plain run."""
    a = build_paper_engine(make_policy("router_default"))
    a.answer_batch(QUERIES, REFS)
    b = build_paper_engine(make_policy("router_default"))
    assert b.telemetry.refined_recall_priors() is None
    b.answer_batch(QUERIES, REFS)
    assert a.telemetry.to_csv() == b.telemetry.to_csv()


# --------------------------------------------------------------------------- #
# Per-backend guardrail thresholds                                             #
# --------------------------------------------------------------------------- #
def test_guardrail_per_backend_confidence_threshold():
    catalog = make_catalog("extended")
    g = Guardrails(
        catalog,
        GuardrailConfig(
            min_retrieval_confidence=0.3,
            min_retrieval_confidence_by_backend={"bm25": 2.5, "ivf": 0.0},
        ),
    )
    assert g.confidence_threshold("dense") == 0.3
    assert g.confidence_threshold("bm25") == 2.5
    assert g.confidence_threshold("ivf") == 0.0

    bm25_i = catalog.index_of("bm25_light")
    dense_i = catalog.index_of("medium_rag")
    ivf_i = catalog.index_of("ivf_medium")
    # BM25-scale score 1.8 < 2.5 → demoted on the lexical scale
    assert g.post_retrieval(bm25_i, 1.8).demoted
    assert not g.post_retrieval(bm25_i, 3.0).demoted
    # cosine 0.35 clears the global 0.3 for dense
    assert not g.post_retrieval(dense_i, 0.35).demoted
    assert g.post_retrieval(dense_i, 0.2).demoted
    # explicit 0.0 disables the guardrail for ivf entirely
    assert not g.post_retrieval(ivf_i, 0.01).demoted
