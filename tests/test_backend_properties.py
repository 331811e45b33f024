"""Bit-identity properties of the device retrieval fast paths.

Every fast path the kernel-grade-backends PR introduced keeps a slower
reference implementation alive as a differential-testing oracle:

* BM25 ``search_batch`` (fused segment-sum + on-device top-k) vs the dense
  ``score_batch`` matrix + host argsort;
* IVF ``impl="bag"`` (flat posting-list gather) vs ``impl="padded"`` (the
  old padded-bucket gather);
* batched hybrid fusion (``_rrf_fuse_rows`` / ``_weighted_fuse_rows``) vs
  the scalar ``rrf_fuse`` / ``weighted_fuse`` dict loops;
* sharded bm25/ivf (replicated global stats + top-k merge) vs unsharded.

Each pair must agree **bitwise** — scores, ids, and row widths — across
batch shapes, score ties, ``k >= corpus``, and empty/no-match queries,
because the serving layer's exact-replay parity (drained streaming ≡
``answer_batch``) is built on rows never moving by a single ulp.

Deterministic seeded sweeps pin known cases; hypothesis fuzzes the same
invariants.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import hypothesis
import hypothesis.strategies as st

from repro.core.bundles import make_catalog
from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.retrieval import (
    BackendStackConfig,
    BM25Index,
    HashedNGramEmbedder,
    IVFIndex,
    ShardedBackend,
    line_passages,
)
from repro.retrieval.backend import BM25Backend, IVFBackend
from repro.retrieval.hybrid import (
    _rrf_fuse_rows,
    _weighted_fuse_rows,
    rrf_fuse,
    weighted_fuse,
)
from repro.serving.engine import build_paper_engine
from repro.serving.streaming import StreamConfig, serve_stream

# Tiny vocabulary on purpose: heavy term overlap manufactures identical
# BM25 scores across passages, exercising the tie-break clauses.
_VOCAB = [
    "alpha", "beta", "gamma", "delta", "kappa", "sigma", "query", "token",
    "index", "probe",
]


def _bm25_corpus(seed: int, n_docs: int):
    rng = np.random.default_rng(seed)
    texts = [
        " ".join(rng.choice(_VOCAB, size=int(rng.integers(3, 9))))
        for _ in range(n_docs)
    ]
    return line_passages("\n".join(texts))


def _bm25_queries(seed: int, nq: int) -> list[str]:
    rng = np.random.default_rng(seed + 1)
    qs = [
        " ".join(rng.choice(_VOCAB, size=int(rng.integers(1, 4))))
        for _ in range(nq)
    ]
    # always exercise the no-match and empty-terms rows
    if nq >= 2:
        qs[-1] = ""
        qs[-2] = "zzzunmatched qqqabsent"
    return qs


def _bm25_oracle(bm: BM25Index, queries, k: int):
    """Reference top-k: dense score matrix + stable host argsort, then the
    sentinel transform (score <= 0 ⇔ no lexical match in that slot)."""
    k = min(k, bm.n_passages)
    dense = bm.score_batch(queries)
    out_s = np.zeros((len(queries), k), np.float32)
    out_i = np.full((len(queries), k), -1, np.int32)
    for r, row in enumerate(dense):
        order = np.argsort(-row, kind="stable")[:k].astype(np.int32)
        s = row[order]
        hit = s > 0.0
        out_s[r] = np.where(hit, s, 0.0)
        out_i[r] = np.where(hit, order, -1)
    return out_s, out_i


def _check_bm25(seed: int, n_docs: int, nq: int, k: int):
    bm = BM25Index(_bm25_corpus(seed, n_docs))
    queries = _bm25_queries(seed, nq)
    ref_s, ref_i = _bm25_oracle(bm, queries, k)
    got_s, got_i = bm.search_batch(queries, k)
    np.testing.assert_array_equal(got_s, ref_s)
    np.testing.assert_array_equal(got_i, ref_i)


# --------------------------------------------------------------------------- #
# BM25: device path ≡ score-matrix oracle                                      #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_docs,k", [(5, 3), (17, 5), (23, 100), (23, 1)])
def test_bm25_device_matches_score_matrix_oracle(seed, n_docs, k):
    """Sweeps tie-heavy corpora × k ≥ corpus × no-match/empty queries."""
    _check_bm25(seed, n_docs, nq=7, k=k)


def test_bm25_rows_bit_identical_across_batch_shapes():
    """A query's row never depends on who it shares a batch with — the
    fixed-shape closure discipline (singles vs 3-wide vs 11-wide batches
    straddling the Q_BLOCK boundary)."""
    bm = BM25Index(_bm25_corpus(3, 23))
    queries = _bm25_queries(3, 11)
    full_s, full_i = bm.search_batch(queries, 6)
    for lo, hi in [(0, 1), (2, 5), (0, 11), (7, 11)]:
        part_s, part_i = bm.search_batch(queries[lo:hi], 6)
        np.testing.assert_array_equal(part_s, full_s[lo:hi])
        np.testing.assert_array_equal(part_i, full_i[lo:hi])


@hypothesis.given(
    st.integers(0, 10_000), st.integers(1, 40), st.integers(1, 9), st.integers(1, 60)
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_bm25_device_oracle_property(seed, n_docs, nq, k):
    _check_bm25(seed, n_docs, nq, k)


# --------------------------------------------------------------------------- #
# IVF: bag gather ≡ padded-bucket oracle                                       #
# --------------------------------------------------------------------------- #
def _ivf_fixture(seed: int, n: int, d: int = 16, n_clusters: int = 4):
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    return IVFIndex.build(jnp.asarray(emb), n_clusters=min(n_clusters, n)), rng


def _canonical(scores: np.ndarray, ids: np.ndarray):
    """Sort each row by (score desc, id asc) — the canonical total order the
    bag path emits natively; applied to the probe-major padded oracle so the
    two are comparable (continuous random scores make real ties measure-zero,
    so canonicalization is a pure permutation)."""
    order = np.lexsort((ids, -scores), axis=-1)
    return (
        np.take_along_axis(scores, order, axis=-1),
        np.take_along_axis(ids, order, axis=-1),
    )


def _check_ivf_bag(seed: int, n: int, k: int, n_probe: int):
    ivf, rng = _ivf_fixture(seed, n)
    q = jnp.asarray(rng.standard_normal((5, 16)).astype(np.float32))
    bs, bi = ivf.search_batch(q, k, n_probe=n_probe, impl="bag")
    ps, pi = ivf.search_batch(q, k, n_probe=n_probe, impl="padded")
    ref_s, ref_i = _canonical(np.asarray(ps, np.float32), np.asarray(pi, np.int32))
    # ids (candidate sets + ordering) must agree exactly; scores only to a
    # couple of ulps — the padded gather's candidate axis (n_probe × cap,
    # rarely a power of two) tiles its d-reduction differently from the
    # bag's pow2-bucketed width, so the two IMPLS round differently. The
    # serving-visible bit-identity contracts (row ≡ across batch shapes,
    # sharded ≡ unsharded, streaming ≡ batch) all compare bag against bag
    # and are asserted exactly elsewhere in this module.
    np.testing.assert_array_equal(np.asarray(bi, np.int32), ref_i)
    np.testing.assert_allclose(np.asarray(bs, np.float32), ref_s, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("n,k,n_probe", [(12, 3, 1), (33, 5, 2), (33, 10, 4), (33, 300, 4)])
def test_ivf_bag_matches_padded_oracle(seed, n, k, n_probe):
    """The flat posting-list gather scores exactly what the padded-bucket
    gather scores — including the -inf/-1 invalid-slot padding when the
    probe set holds fewer than k members (k=300 case)."""
    _check_ivf_bag(seed, n, k, n_probe)


@hypothesis.given(
    st.integers(0, 10_000), st.integers(4, 50), st.integers(1, 60), st.integers(1, 4)
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_ivf_bag_oracle_property(seed, n, k, n_probe):
    _check_ivf_bag(seed, n, k, n_probe)


def test_ivf_bag_rows_bit_identical_across_batch_shapes():
    ivf, rng = _ivf_fixture(11, 29)
    q = rng.standard_normal((11, 16)).astype(np.float32)
    fs, fi = ivf.search_batch(jnp.asarray(q), 6, n_probe=2)
    fs, fi = np.asarray(fs), np.asarray(fi)
    for lo, hi in [(0, 1), (3, 7), (8, 11)]:
        ps, pi = ivf.search_batch(jnp.asarray(q[lo:hi]), 6, n_probe=2)
        np.testing.assert_array_equal(np.asarray(ps), fs[lo:hi])
        np.testing.assert_array_equal(np.asarray(pi), fi[lo:hi])


def test_ivf_canonical_order_under_duplicate_embeddings():
    """Duplicated embeddings force exact score ties; the bag path must order
    them by ascending passage id (the protocol's total order)."""
    rng = np.random.default_rng(5)
    base = rng.standard_normal((6, 16)).astype(np.float32)
    emb = np.concatenate([base, base, base])  # every score appears 3×
    ivf = IVFIndex.build(jnp.asarray(emb), n_clusters=2)
    s, i = ivf.search_batch(jnp.asarray(base[:3]), 18, n_probe=2)
    s, i = np.asarray(s), np.asarray(i)
    for srow, irow in zip(s, i):
        fin = np.isfinite(srow)
        sf, if_ = srow[fin], irow[fin]
        assert np.all(sf[:-1] >= sf[1:])
        tie = sf[:-1] == sf[1:]
        assert np.all(if_[:-1][tie] < if_[1:][tie])


# --------------------------------------------------------------------------- #
# Hybrid: batched fusion ≡ scalar dict-loop oracles                            #
# --------------------------------------------------------------------------- #
def _fusion_inputs(seed: int, n: int, m: int, ms: int, size: int):
    """Random candidate rows shaped like HybridRetriever's inputs: unique
    descending dense rows, sparse rows with a sentinel suffix."""
    rng = np.random.default_rng(seed)
    d_ids = np.stack([rng.permutation(size)[:m] for _ in range(n)]).astype(np.int32)
    d_scores = -np.sort(-rng.random((n, m)).astype(np.float32), axis=1)
    s_ids = np.stack([rng.permutation(size)[:ms] for _ in range(n)]).astype(np.int32)
    s_scores = -np.sort(-(rng.random((n, ms)).astype(np.float32) + 0.1), axis=1)
    # give some rows a sentinel tail (BM25 ran dry), one row fully sentinel
    for r in range(n):
        n_sent = int(rng.integers(0, ms))
        if r == 0:
            n_sent = ms
        if n_sent:
            s_ids[r, ms - n_sent :] = -1
            s_scores[r, ms - n_sent :] = 0.0
    return d_scores, d_ids, s_scores, s_ids


def _check_fusion_rows(seed: int, n: int, m: int, ms: int, k: int, size: int):
    d_scores, d_ids, s_scores, s_ids = _fusion_inputs(seed, n, m, ms, size)
    kk = min(k, m)  # HybridRetriever guarantees m >= k real dense candidates

    got_s, got_i = _rrf_fuse_rows(d_scores, d_ids, s_ids, kk, size)
    for r in range(n):
        real = s_ids[r] >= 0
        _, ref_i = rrf_fuse(
            [(d_scores[r], d_ids[r]), (s_scores[r][real], s_ids[r][real])], kk
        )
        np.testing.assert_array_equal(got_i[r], ref_i)
        dense_map = {int(p): float(s) for p, s in zip(d_ids[r], d_scores[r])}
        ref_rep = np.array(
            [dense_map.get(int(p), 0.0) for p in ref_i], np.float32
        )
        np.testing.assert_array_equal(got_s[r], ref_rep)

    got_s, got_i = _weighted_fuse_rows(
        d_scores, d_ids, s_scores, s_ids, kk, size, w_dense=0.6
    )
    for r in range(n):
        real = s_ids[r] >= 0
        ref_s, ref_i = weighted_fuse(
            (d_scores[r], d_ids[r]),
            (s_scores[r][real], s_ids[r][real]),
            kk,
            w_dense=0.6,
        )
        np.testing.assert_array_equal(got_i[r], ref_i)
        np.testing.assert_array_equal(got_s[r], ref_s)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_batched_fusion_matches_scalar_oracles(seed):
    """Both fusions, per row, bitwise — duplicate ids merged across lists,
    sentinel tails excluded from aggregation and normalization."""
    _check_fusion_rows(seed, n=6, m=8, ms=8, k=5, size=40)
    _check_fusion_rows(seed + 100, n=4, m=5, ms=3, k=4, size=12)


@hypothesis.given(
    st.integers(0, 10_000), st.integers(1, 8), st.integers(1, 10), st.integers(1, 10)
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_batched_fusion_oracle_property(seed, n, m, ms):
    size = max(m, ms) * 3
    _check_fusion_rows(seed, n, m, ms, k=m, size=size)


# --------------------------------------------------------------------------- #
# Sharded sparse ≡ unsharded (replicated global stats)                         #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n_shards", [1, 2, 3, 5])
@pytest.mark.parametrize("k", [1, 5, 100])
def test_sharded_bm25_bitwise_equal_unsharded(n_shards, k):
    passages = _bm25_corpus(2, 23)
    plain = BM25Backend(BM25Index(passages), passages)
    sharded = ShardedBackend.from_bm25(plain, n_shards=n_shards)
    queries = _bm25_queries(2, 7)
    ps, pi = plain.search_batch(queries, None, k)
    ss, si = sharded.search_batch(queries, None, k)
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(ps, np.float32))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pi, np.int32))


@pytest.mark.parametrize("n_shards", [1, 2, 3, 5])
@pytest.mark.parametrize("k", [1, 5, 100])
def test_sharded_ivf_bitwise_equal_unsharded(n_shards, k):
    ivf, rng = _ivf_fixture(4, 27)
    plain = IVFBackend(ivf, n_probe=2)
    sharded = ShardedBackend.from_ivf(plain, n_shards=n_shards)
    q = jnp.asarray(rng.standard_normal((6, 16)).astype(np.float32))
    ps, pi = plain.search_batch(None, q, k)
    ss, si = sharded.search_batch(None, q, k)
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(ps, np.float32))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pi, np.int32))


@hypothesis.given(
    st.integers(0, 10_000), st.integers(5, 40), st.integers(1, 5), st.integers(1, 50)
)
@hypothesis.settings(max_examples=15, deadline=None)
def test_sharded_sparse_identity_property(seed, n, n_shards, k):
    hypothesis.assume(n_shards <= n)
    passages = _bm25_corpus(seed, n)
    plain = BM25Backend(BM25Index(passages), passages)
    sharded = ShardedBackend.from_bm25(plain, n_shards=n_shards)
    queries = _bm25_queries(seed, 4)
    ps, pi = plain.search_batch(queries, None, k)
    ss, si = sharded.search_batch(queries, None, k)
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(ps, np.float32))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(pi, np.int32))


# --------------------------------------------------------------------------- #
# End to end: drained streaming ≡ answer_batch under sharded sparse backends   #
# --------------------------------------------------------------------------- #
def test_streaming_parity_extended_catalog_with_sharded_sparse():
    """The whole-pipeline exactness claim: an extended-catalog engine whose
    bm25/ivf/dense backends are ALL 3-way sharded produces byte-identical
    telemetry to (a) its own answer_batch run and (b) a completely
    unsharded engine — sparse sharding is invisible end to end."""
    queries, refs = list(BENCHMARK_QUERIES), list(REFERENCE_ANSWERS)
    policy = lambda: make_policy("router_default", catalog=make_catalog("extended"))  # noqa: E731
    stack = BackendStackConfig(shards=3, shard_backends=("dense", "bm25", "ivf"))

    plain = build_paper_engine(policy())
    plain.answer_batch(queries, refs)

    batch = build_paper_engine(policy(), stack=stack)
    batch.answer_batch(queries, refs)
    assert batch.telemetry.to_csv() == plain.telemetry.to_csv()

    stream = build_paper_engine(policy(), stack=stack)
    result = serve_stream(
        stream, queries, refs, config=StreamConfig(overlap=True, microbatch_max=4)
    )
    assert len(result.responses) == len(queries)
    assert stream.telemetry.to_csv() == plain.telemetry.to_csv()
