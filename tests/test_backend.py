"""Pluggable retrieval backends: protocol adapters, batched paths, the
backend-aware catalog, and mixed-backend serving parity.

The tentpole contracts (retrieval/backend.py + the backend-threaded stack):

* Every adapter honors one batched entry point
  ``search_batch(queries, query_vecs, k)`` with descending rows, ids into
  the shared corpus, and k clamped to the corpus size — and each row is a
  pure function of (corpus, query, k), never of batch shape.
* ``DenseBackend`` is bit-identical to calling ``DenseIndex`` directly, so
  the paper catalog's records cannot move (the committed Appendix-F CSVs
  stay byte-identical — pinned end-to-end by the serve CLI run).
* The extended catalog routes the 28-query paper benchmark through all
  four backends under ``router_default``, and drained streaming runs stay
  bit-identical to ``answer_batch`` under that mixed-backend catalog at
  every (pipeline_depth, retrieval_workers) setting.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import hypothesis
import hypothesis.strategies as st

from repro.core.bundles import Bundle, BundleCatalog, DEFAULT_CATALOG, make_catalog
from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS, corpus_document
from repro.retrieval import (
    BM25Index,
    BM25Params,
    BackendCost,
    DenseBackend,
    DenseIndex,
    HashedNGramEmbedder,
    HybridRetriever,
    IVFBackend,
    IVFIndex,
    RetrievalBackend,
    backend_cost,
    line_passages,
    make_backends,
    rrf_fuse,
    weighted_fuse,
)
from repro.serving.engine import RAGEngine, build_paper_engine
from repro.serving.streaming import StreamConfig, serve_stream

EMB = HashedNGramEmbedder(dim=128)
QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)


def _corpus():
    passages = line_passages(corpus_document())
    index, _ = DenseIndex.build(passages, EMB)
    return passages, index


# --------------------------------------------------------------------------- #
# Cost descriptors                                                             #
# --------------------------------------------------------------------------- #
def test_backend_cost_validation_and_registry():
    with pytest.raises(ValueError):
        BackendCost(latency_scale=0.0)
    with pytest.raises(ValueError):
        BackendCost(recall_prior=0.0)
    with pytest.raises(ValueError):
        BackendCost(recall_prior=1.5)
    # dense is the calibration anchor: exact identities for the paper catalog
    assert backend_cost("dense").latency_scale == 1.0
    assert backend_cost("dense").recall_prior == 1.0
    # unknown names degrade to the neutral descriptor (future backends)
    assert backend_cost("sharded_remote_v2") == BackendCost()
    assert BackendCost(flops_per_item=2.0).flops_per_query(100) == 200.0


def test_all_adapters_satisfy_protocol():
    passages, index = _corpus()
    backends = make_backends(
        index, passages, EMB, names=("dense", "bm25", "ivf", "hybrid")
    )
    assert set(backends) == {"dense", "bm25", "ivf", "hybrid"}
    for name, b in backends.items():
        assert isinstance(b, RetrievalBackend)
        assert b.name == name
        assert b.size == len(passages)
        qv = EMB.embed(QUERIES[:3]) if b.requires_query_vecs else None
        scores, ids = b.search_batch(QUERIES[:3], qv, 4)
        scores, ids = np.asarray(scores), np.asarray(ids)
        assert scores.shape == ids.shape == (3, 4)
        # ids are valid passage ids, or the explicit empty-slot sentinel
        # (id=-1, score=0.0) forming a row suffix (the backend contract)
        assert ((ids >= -1) & (ids < len(passages))).all()
        sent = ids < 0
        assert (scores[sent] == 0.0).all()
        for row in sent:
            first = int(np.argmax(row)) if row.any() else len(row)
            assert not row[:first].any() and row[first:].all()
        if name != "hybrid":
            # rows descend by the reported score (hybrid's RRF rows rank by
            # fused reciprocal rank but report dense-cosine confidence)
            assert (np.diff(scores, axis=-1) <= 1e-6).all()
        real0 = ids[0][ids[0] >= 0]
        assert len(b.get_passages(real0)) == len(real0)
    assert not backends["bm25"].requires_query_vecs
    with pytest.raises(ValueError):
        make_backends(index, passages, EMB, names=("warp_drive",))


def test_dense_backend_is_pure_delegation():
    passages, index = _corpus()
    backend = DenseBackend(index)
    qv = EMB.embed(QUERIES[:5])
    s_b, i_b = backend.search_batch(QUERIES[:5], qv, 4)
    s_i, i_i = index.search_batch(qv, 4)
    np.testing.assert_array_equal(np.asarray(s_b), np.asarray(s_i))
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_i))


# --------------------------------------------------------------------------- #
# Satellite: BM25 defaults + batched path                                      #
# --------------------------------------------------------------------------- #
def test_bm25_params_constructed_per_instance():
    passages, _ = _corpus()
    a, b = BM25Index(passages), BM25Index(passages)
    assert a.params == BM25Params() and a.params is not b.params
    custom = BM25Index(passages, BM25Params(k1=2.0))
    assert custom.params.k1 == 2.0


@pytest.mark.parametrize("nq", [1, 3, 5, 7])  # incl. non-divisible shapes
def test_bm25_search_batch_matches_single(nq):
    passages, _ = _corpus()
    bm = BM25Index(passages)
    queries = QUERIES[:nq]
    scores, ids = bm.search_batch(queries, 4)
    assert scores.shape == ids.shape == (nq, 4)
    for r, q in enumerate(queries):
        s1, i1 = bm.search(q, 4)
        np.testing.assert_array_equal(ids[r], i1)
        np.testing.assert_array_equal(scores[r], s1)


def test_bm25_search_batch_k_clamps_and_empty_terms():
    passages, _ = _corpus()
    bm = BM25Index(passages)
    scores, ids = bm.search_batch(["FAISS index", ""], k=100)  # k > corpus
    assert scores.shape == (2, len(passages))
    # row 0: the matching passages lead (descending, strictly positive),
    # then the explicit empty-slot sentinel (-1, 0.0) fills the tail —
    # "no lexical hit" is now distinguishable from "passage 0 scored 0"
    n_hits = int((scores[0] > 0).sum())
    assert 0 < n_hits < len(passages)
    hit_ids = ids[0][:n_hits]
    assert len(set(hit_ids.tolist())) == n_hits and (hit_ids >= 0).all()
    np.testing.assert_array_equal(ids[0][n_hits:], -1)
    np.testing.assert_array_equal(scores[0][n_hits:], 0.0)
    # no matching terms: a full sentinel row
    assert scores[1].max() == 0.0
    np.testing.assert_array_equal(ids[1], np.full(len(passages), -1))


def test_bm25_row_independent_of_batch_shape():
    passages, _ = _corpus()
    bm = BM25Index(passages)
    alone = bm.search_batch([QUERIES[0]], 5)
    batched = bm.search_batch(QUERIES[:6], 5)
    np.testing.assert_array_equal(alone[0][0], batched[0][0])
    np.testing.assert_array_equal(alone[1][0], batched[1][0])


# --------------------------------------------------------------------------- #
# Satellite: hybrid batched path                                               #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fusion", ["rrf", "weighted"])
def test_hybrid_search_batch_matches_single(fusion):
    passages, index = _corpus()
    hybrid = HybridRetriever(index, BM25Index(passages), EMB, fusion=fusion)
    nq = 5  # non-divisible by the dense path's Q_BLOCK=8
    scores, ids = hybrid.search_batch(QUERIES[:nq], 4)
    assert scores.shape == ids.shape == (nq, 4)
    for r, q in enumerate(QUERIES[:nq]):
        res = hybrid.search(q, 4)
        np.testing.assert_array_equal(ids[r], res.passage_ids)
        np.testing.assert_array_equal(scores[r], res.scores)


def test_hybrid_search_batch_k_clamps_and_reuses_vecs():
    passages, index = _corpus()
    hybrid = HybridRetriever(index, BM25Index(passages), EMB)
    scores, ids = hybrid.search_batch(QUERIES[:2], k=999)  # k > corpus
    assert scores.shape == (2, len(passages))
    assert sorted(ids[0].tolist()) == list(range(len(passages)))
    # pre-embedded vectors short-circuit the embed call and change nothing
    qv = EMB.embed(QUERIES[:2])
    s2, i2 = hybrid.search_batch(QUERIES[:2], k=999, query_vecs=np.asarray(qv))
    np.testing.assert_array_equal(ids, i2)
    np.testing.assert_array_equal(scores, s2)


# --------------------------------------------------------------------------- #
# Satellite: fusion property tests                                             #
# --------------------------------------------------------------------------- #
def _ranked_list(ids, seed):
    """Distinct ids with strictly decreasing synthetic scores."""
    rng = np.random.default_rng(seed)
    scores = np.sort(rng.uniform(0.1, 10.0, size=len(ids)))[::-1]
    return scores.astype(np.float32), np.asarray(ids, np.int32)


@hypothesis.given(
    st.lists(st.integers(0, 30), min_size=1, max_size=8, unique=True),
    st.lists(st.integers(0, 30), min_size=1, max_size=8, unique=True),
    st.integers(1, 6),
)
@hypothesis.settings(max_examples=40, deadline=None)
def test_rrf_fuse_permutation_invariant_and_scale_stable(ids_a, ids_b, k):
    a, b = _ranked_list(ids_a, 1), _ranked_list(ids_b, 2)
    s1, i1 = rrf_fuse([a, b], k)
    # permutation-invariant in the list order
    s2, i2 = rrf_fuse([b, a], k)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2)
    # rank-based: positive rescaling of either list's scores changes nothing
    a_scaled = (a[0] * 37.5, a[1])
    b_scaled = (b[0] * 0.003, b[1])
    s3, i3 = rrf_fuse([a_scaled, b_scaled], k)
    np.testing.assert_array_equal(i1, i3)
    np.testing.assert_allclose(s1, s3)


@hypothesis.given(
    st.lists(st.integers(0, 30), min_size=2, max_size=8, unique=True),
    st.lists(st.integers(0, 30), min_size=2, max_size=8, unique=True),
    st.integers(1, 6),
    st.floats(0.01, 100.0),
)
@hypothesis.settings(max_examples=40, deadline=None)
def test_weighted_fuse_scale_invariant_and_symmetric(ids_a, ids_b, k, scale):
    a, b = _ranked_list(ids_a, 3), _ranked_list(ids_b, 4)
    s1, i1 = weighted_fuse(a, b, k)
    # min-max normalization absorbs any positive affine scaling per list
    s2, i2 = weighted_fuse((a[0] * scale, a[1]), (b[0] * np.float32(0.5), b[1]), k)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(s1, s2, rtol=1e-5)
    # at w_dense=0.5 the two lists are exchangeable
    s3, i3 = weighted_fuse(b, a, k, w_dense=0.5)
    np.testing.assert_array_equal(i1, i3)
    np.testing.assert_allclose(s1, s3, rtol=1e-5)


# --------------------------------------------------------------------------- #
# Satellite: IVF recall monotonicity + batch-shape invariance                  #
# --------------------------------------------------------------------------- #
def test_ivf_recall_monotonic_in_n_probe():
    rng = np.random.default_rng(5)
    emb = jnp.asarray(rng.normal(size=(256, 32)).astype(np.float32))
    ivf = IVFIndex.build(emb, n_clusters=8, key=jax.random.PRNGKey(2))
    q = jnp.asarray(rng.normal(size=(6, 32)).astype(np.float32))
    recalls = [ivf.recall_vs_exact(q, k=5, n_probe=p) for p in range(1, 9)]
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:]))
    assert recalls[-1] == 1.0  # full probe == exact


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=10, deadline=None)
def test_ivf_recall_monotonic_property(seed):
    rng = np.random.default_rng(seed)
    emb = jnp.asarray(rng.normal(size=(96, 16)).astype(np.float32))
    ivf = IVFIndex.build(emb, n_clusters=6, key=jax.random.PRNGKey(seed % 7))
    q = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    recalls = [ivf.recall_vs_exact(q, k=4, n_probe=p) for p in (1, 3, 6)]
    assert recalls[0] <= recalls[1] + 1e-9 <= recalls[2] + 2e-9
    assert recalls[-1] == 1.0


def test_ivf_backend_cost_monotonic_in_n_probe():
    passages, index = _corpus()
    ivf = IVFIndex.build(index.embeddings, n_clusters=4, key=jax.random.PRNGKey(0))
    costs = [IVFBackend(ivf, passages, n_probe=p).cost for p in (1, 2, 4)]
    assert costs[0].recall_prior < costs[1].recall_prior < costs[2].recall_prior == 1.0
    assert costs[0].latency_scale < costs[1].latency_scale < costs[2].latency_scale
    with pytest.raises(ValueError):
        IVFBackend(ivf, passages, n_probe=0)


def test_ivf_search_row_independent_of_batch_shape():
    """A query's IVF scores are bit-identical alone vs inside any batch —
    the fixed Q_BLOCK chunking contract the mixed-backend serving parity
    relies on (XLA tiles shape-(nq, d) matmuls differently per nq)."""
    rng = np.random.default_rng(9)
    emb = jnp.asarray(rng.normal(size=(200, 32)).astype(np.float32))
    ivf = IVFIndex.build(emb, n_clusters=8, key=jax.random.PRNGKey(3))
    qs = jnp.asarray(rng.normal(size=(11, 32)).astype(np.float32))  # non-divisible
    v_all, i_all = ivf.search_batch(qs, k=5, n_probe=3)
    for r in (0, 7, 10):
        v1, i1 = ivf.search_batch(qs[r : r + 1], k=5, n_probe=3)
        np.testing.assert_array_equal(np.asarray(v_all)[r], np.asarray(v1)[0])
        np.testing.assert_array_equal(np.asarray(i_all)[r], np.asarray(i1)[0])


# --------------------------------------------------------------------------- #
# Backend-aware catalog                                                        #
# --------------------------------------------------------------------------- #
def test_paper_catalog_arrays_are_backend_neutral():
    """Dense scaling is an exact identity: the paper catalog's arrays carry
    the raw Table-I priors bit-for-bit, plus all-ones backend columns."""
    arrs = DEFAULT_CATALOG.as_arrays()
    np.testing.assert_array_equal(
        np.asarray(arrs["latency_prior_ms"]), [8.0, 45.0, 60.0, 95.0]
    )
    np.testing.assert_array_equal(np.asarray(arrs["backend_recall"]), np.ones(4))
    np.testing.assert_array_equal(np.asarray(arrs["backend_latency_scale"]), np.ones(4))
    assert DEFAULT_CATALOG.backends_used() == ("dense",)
    assert DEFAULT_CATALOG.backend_names == ("dense",) * 4


def test_extended_catalog_structure():
    cat = make_catalog("extended")
    assert cat.names[:4] == DEFAULT_CATALOG.names  # paper prefix intact
    assert [cat[n] for n in cat.names[:4]] == list(DEFAULT_CATALOG)
    assert cat.backends_used() == ("dense", "bm25", "ivf", "hybrid")
    arrs = cat.as_arrays()
    # backend scaling discriminates the new bundles
    assert float(arrs["latency_prior_ms"][cat.index_of("bm25_light")]) == pytest.approx(
        45.0 * 0.25
    )
    assert float(arrs["backend_recall"][cat.index_of("ivf_medium")]) < 1.0
    with pytest.raises(ValueError):
        make_catalog("bogus")
    with pytest.raises(ValueError):
        Bundle("bad", 3, False, 0.5, 10, 100, backend="")


def test_effective_priors_feed_utility():
    """The recall discount must actually move Eq. 1: an identical bundle on
    a lossier backend scores strictly lower utility."""
    from repro.core.router import Router

    base = Bundle("a_dense", 5, False, 0.8, 60.0, 275.0, depth_affinity=0.0)
    lossy = Bundle("b_ivf", 5, False, 0.8, 60.0, 275.0, depth_affinity=0.0, backend="ivf")
    router = Router(BundleCatalog([base, lossy]))
    # overrides pin latency/cost equal, isolating the recall discount
    same = np.asarray([100.0, 100.0], np.float32)
    _, util = router.route_batch_np(np.asarray([0.3]), latency_override=same, cost_override=same)
    assert util[0, 0] > util[0, 1]
    # without overrides the static priors are backend-scaled: the ivf
    # bundle's latency prior must come in below the dense twin's
    arrs = router.catalog.as_arrays()
    assert float(arrs["latency_prior_ms"][1]) < float(arrs["latency_prior_ms"][0])


# --------------------------------------------------------------------------- #
# Mixed-backend serving: coverage + parity                                     #
# --------------------------------------------------------------------------- #
def _extended_engine():
    return build_paper_engine(make_policy("router_default", catalog=make_catalog("extended")))


_EXT_REF: dict = {}


def _extended_reference() -> str:
    if not _EXT_REF:
        eng = _extended_engine()
        for q, r in zip(QUERIES, REFS):
            eng.answer(q, reference=r)
        _EXT_REF["csv"] = eng.telemetry.to_csv()
        _EXT_REF["counts"] = eng.telemetry.strategy_counts()
    return _EXT_REF["csv"]


def test_extended_catalog_routes_all_four_backends():
    """Acceptance criterion: one router_default pass over the 28-query
    benchmark exercises dense, bm25, ivf, and hybrid retrieval."""
    _extended_reference()
    cat = make_catalog("extended")
    by_backend: dict[str, int] = {}
    for name, n in _EXT_REF["counts"].items():
        b = cat[name]
        if not b.skip_retrieval:
            by_backend[b.backend] = by_backend.get(b.backend, 0) + n
    assert all(by_backend.get(k, 0) >= 1 for k in ("dense", "bm25", "ivf", "hybrid")), by_backend


def test_extended_batched_matches_sequential():
    eng = _extended_engine()
    eng.answer_batch(QUERIES, REFS)
    assert eng.telemetry.to_csv() == _extended_reference()


@pytest.mark.parametrize("depth,workers,microbatch", [(1, 1, 5), (2, 2, 5), (4, 2, 3)])
def test_extended_streaming_parity_swept(depth, workers, microbatch):
    """Acceptance criterion: drained streaming == answer_batch, bit-exact,
    under the mixed-backend catalog at every pipeline shape."""
    eng = _extended_engine()
    result = serve_stream(
        eng,
        QUERIES,
        REFS,
        config=StreamConfig(
            overlap=depth > 1,
            pipeline_depth=depth,
            retrieval_workers=workers,
            microbatch_max=microbatch,
        ),
    )
    assert len(result.responses) == len(QUERIES) and not result.rejections
    assert eng.telemetry.to_csv() == _extended_reference()
    # per-backend counters cover every backend the catalog routed through
    assert set(result.retrieve_calls_by_backend) == {"dense", "bm25", "ivf", "hybrid"}
    assert sum(result.retrieve_calls_by_backend.values()) == result.retrieve_calls


def test_bm25_bundle_never_bills_embedding():
    """BM25 retrieval spends no embed call: embedding_tokens is 0 on its
    records (vector-backed grounded bundles keep billing τ_embed)."""
    _extended_reference()
    eng = _extended_engine()
    eng.answer_batch(QUERIES, REFS)
    cat = make_catalog("extended")
    saw_bm25 = saw_dense = False
    for r in eng.telemetry.records:
        b = cat[r.strategy]
        if b.skip_retrieval:
            continue
        if b.backend == "bm25":
            saw_bm25 = True
            assert r.embedding_tokens == 0
        elif cat[r.strategy].backend in ("dense", "ivf", "hybrid"):
            saw_dense = True
            assert r.embedding_tokens > 0
    assert saw_bm25 and saw_dense


def test_engine_rejects_catalog_with_missing_backend():
    passages, index = _corpus()
    cat = BundleCatalog(
        tuple(DEFAULT_CATALOG)
        + (Bundle("bm25_x", 3, False, 0.6, 40.0, 200.0, backend="bm25"),)
    )
    with pytest.raises(ValueError, match="bm25"):
        RAGEngine(make_policy("router_default", catalog=cat), index, EMB, catalog=cat)


def test_paper_engine_backends_default_to_dense():
    eng = build_paper_engine(make_policy("router_default"))
    assert set(eng.backends) == {"dense"}
    assert isinstance(eng.backends["dense"], DenseBackend)
    assert eng.backends["dense"].index is eng.index


def test_middle_stages_pure_under_mixed_backends():
    """The stage-purity contract (what licenses worker threads) holds for
    every backend, not just dense: retrieve twice on one artifact → equal
    rows, zero engine mutation."""
    from repro.serving import stages

    eng = _extended_engine()
    routed = stages.route(eng, QUERIES[:12], REFS[:12])
    assert {b for b, _k in routed.retrieval_plan} >= {"bm25", "ivf"} or len(
        routed.retrieval_plan
    )  # plan shape depends on routing; purity check below is the contract
    records_before = len(eng.telemetry.records)
    r1 = stages.retrieve(eng, routed)
    r2 = stages.retrieve(eng, routed)
    assert r1.search_calls == r2.search_calls
    assert r1.search_calls_by_backend == r2.search_calls_by_backend
    for i in r1.retrievals:
        np.testing.assert_array_equal(r1.retrievals[i][0], r2.retrievals[i][0])
        np.testing.assert_array_equal(r1.retrievals[i][1], r2.retrievals[i][1])
    d1 = stages.decode(eng, stages.assemble(eng, r1))
    d2 = stages.decode(eng, stages.assemble(eng, r2))
    assert [str(dataclasses.asdict(e)) for e in d1.executions] == [
        str(dataclasses.asdict(e)) for e in d2.executions
    ]
    assert len(eng.telemetry.records) == records_before
