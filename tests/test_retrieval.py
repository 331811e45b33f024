"""Tests for the retrieval substrate: tokenizer, chunking, embedder, dense
index, blocked/distributed top-k, BM25, IVF, hybrid fusion."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import hypothesis
import hypothesis.strategies as st

from repro.data import BENCHMARK_CORPUS, BENCHMARK_QUERIES, corpus_document
from repro.retrieval import (
    BM25Index,
    DenseIndex,
    HashedNGramEmbedder,
    HybridRetriever,
    IVFIndex,
    Passage,
    blocked_topk,
    count_tokens,
    kmeans,
    lexical_overlap,
    l2_normalize,
    line_passages,
    merge_topk,
    rrf_fuse,
    sliding_window_passages,
    terms,
    weighted_fuse,
)
from repro.retrieval.index import _block_width, search_program
from repro.retrieval.topk import mips_scores

EMB = HashedNGramEmbedder(dim=128)


def _paper_index():
    passages = line_passages(corpus_document())
    idx, tokens = DenseIndex.build(passages, EMB)
    return idx, passages, tokens


# --------------------------------------------------------------------------- #
# Tokenizer                                                                    #
# --------------------------------------------------------------------------- #
def test_count_tokens_deterministic_and_positive():
    q = "What is FAISS used for?"
    assert count_tokens(q) == count_tokens(q) > 0
    assert count_tokens("") == 0


def test_count_tokens_scales_with_length():
    assert count_tokens(corpus_document()) > count_tokens(BENCHMARK_CORPUS[0])


def test_terms_stemming_and_stopwords():
    assert terms("retrieval strategies") == ["retrieval", "strategy"]
    assert "the" not in terms("the documents", remove_stopwords=True)


def test_lexical_overlap_bounds_and_identity():
    ref = BENCHMARK_CORPUS[0]
    assert lexical_overlap(ref, ref) == 1.0
    assert lexical_overlap("completely unrelated words here", ref) < 0.3
    assert lexical_overlap("", ref) == 0.0


@hypothesis.given(st.text(max_size=200))
@hypothesis.settings(max_examples=30, deadline=None)
def test_overlap_in_unit_interval(ans):
    v = lexical_overlap(ans, BENCHMARK_CORPUS[3])
    assert 0.0 <= v <= 1.0


# --------------------------------------------------------------------------- #
# Chunking                                                                     #
# --------------------------------------------------------------------------- #
def test_line_passages_paper_corpus_is_15():
    ps = line_passages(corpus_document())
    assert len(ps) == 15  # paper Table II
    assert ps[0].text == BENCHMARK_CORPUS[0]
    assert [p.passage_id for p in ps] == list(range(15))


def test_line_passages_skips_blank_lines():
    ps = line_passages("a\n\n  \nb\n")
    assert [p.text for p in ps] == ["a", "b"]


def test_sliding_window_covers_document():
    doc = " ".join(f"w{i}" for i in range(200))
    ps = sliding_window_passages(doc, window_words=64, stride_words=48)
    assert ps[0].text.startswith("w0 ")
    assert "w199" in ps[-1].text
    with pytest.raises(ValueError):
        sliding_window_passages(doc, window_words=0)


# --------------------------------------------------------------------------- #
# Embedder                                                                     #
# --------------------------------------------------------------------------- #
def test_embedder_unit_norm_and_shape():
    v = EMB.embed(list(BENCHMARK_CORPUS))
    assert v.shape == (15, 128)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(v), axis=-1), 1.0, atol=1e-5)


def test_embedder_deterministic_across_calls():
    a = np.asarray(EMB.embed(["What is RAG?"]))
    b = np.asarray(HashedNGramEmbedder(dim=128).embed(["What is RAG?"]))
    np.testing.assert_allclose(a, b)


def test_embedder_similarity_tracks_lexical_overlap():
    v = EMB.embed(["retrieval augmented generation", "retrieval augmented generation system", "capybara swimming lessons"])
    sims = np.asarray(v @ v.T)
    assert sims[0, 1] > sims[0, 2]


def test_embedder_empty_batch():
    assert EMB.embed([]).shape == (0, 128)


# --------------------------------------------------------------------------- #
# Dense index + top-k                                                          #
# --------------------------------------------------------------------------- #
def test_dense_index_self_retrieval():
    idx, passages, index_tokens = _paper_index()
    assert idx.size == 15 and index_tokens > 0
    # each corpus line's own embedding must retrieve itself at rank 1
    for pid, p in enumerate(passages):
        r = idx.search(EMB.embed([p.text])[0], k=1)
        assert int(r.passage_ids[0]) == pid
        assert r.confidence == pytest.approx(1.0, abs=1e-4)


def test_dense_search_query_relevance():
    idx, passages, _ = _paper_index()
    r = idx.search(EMB.embed(["What is FAISS used for?"])[0], k=3)
    texts = " ".join(p.text for p in idx.get_passages(r.passage_ids))
    assert "FAISS" in texts


def test_search_batch_matches_single():
    idx, _, _ = _paper_index()
    qs = EMB.embed(list(BENCHMARK_QUERIES[:6]))
    sb, ib = idx.search_batch(qs, k=4)
    for i in range(6):
        r = idx.search(qs[i], k=4)
        np.testing.assert_array_equal(np.asarray(ib[i]), r.passage_ids)


def test_blocked_topk_matches_lax_topk():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 10_000)).astype(np.float32))
    for k in (1, 7, 64):
        bv, bi = blocked_topk(x, k, block=1024)
        lv, li = jax.lax.top_k(x, k)
        np.testing.assert_allclose(np.asarray(bv), np.asarray(lv), rtol=1e-6)
        # values identical; indices may differ only among ties
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(x), np.asarray(bi), -1), np.asarray(lv), rtol=1e-6
        )


def test_blocked_topk_k_larger_than_n_raises():
    with pytest.raises(ValueError):
        blocked_topk(jnp.zeros((4,)), 8)


@hypothesis.given(st.integers(min_value=1, max_value=16), st.integers(min_value=17, max_value=400))
@hypothesis.settings(max_examples=20, deadline=None)
def test_blocked_topk_property(k, n):
    rng = np.random.default_rng(k * 1000 + n)
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    bv, _ = blocked_topk(x, k, block=32)
    np.testing.assert_allclose(np.asarray(bv), np.sort(np.asarray(x))[::-1][:k], rtol=1e-6)


def _tied_rows(shape, seed, n_inf=40, offset=0.0):
    """Integer-valued scores, most of them tied, fewer the higher they lie
    (so a top-k runs through ties across groups whose maxima differ), whose
    last ``n_inf`` columns are ``-inf``, as the masked pad rows of a search
    are."""
    x = np.floor(np.random.default_rng(seed).exponential(2.0, size=shape)) + offset
    x = x.astype(np.float32)
    x[..., x.shape[-1] - n_inf :] = -np.inf
    return x


def _row_case(make, k, **kw):
    def run():
        x = jnp.asarray(make())
        return (*blocked_topk(x, k, **kw), x, k)

    return run


def _search_case(k):
    """``search_program`` over a corpus of repeated rows (tied scores), its
    last block masked past ``n_valid``, against ``lax.top_k`` of the same
    scores."""

    def run():
        n_valid, dim = 6_000, 16
        rng = np.random.default_rng(k)
        rows = np.asarray(l2_normalize(jnp.asarray(rng.normal(size=(50, dim)), jnp.float32)))
        corpus = np.zeros((n_valid + (-n_valid) % _block_width(k), dim), np.float32)
        corpus[:n_valid] = rows[rng.integers(0, 50, size=n_valid)]
        q = jnp.asarray(np.concatenate([rows[:4], rng.normal(size=(4, dim))]), jnp.float32)
        corpus = jnp.asarray(corpus)
        vals, ids = search_program(k, n_valid)(corpus, q)
        scores = jax.jit(lambda c, q: mips_scores(l2_normalize(q), c))(corpus, q)
        scores = jnp.where(jnp.arange(corpus.shape[0]) < n_valid, scores, -jnp.inf)
        return vals, ids, scores, k

    return run


def _bit_pattern_rows():
    """Random f32 bit patterns salted with ±0, ±inf and NaNs of both signs."""
    rng = np.random.default_rng(7)
    bits = rng.integers(-(2**31), 2**31, size=(3, 6_000), dtype=np.int64).astype(np.int32)
    salt = np.array([0, -(2**31), 0x7FC00000, -0x400000, 0x7F800000, -0x800000], np.int32)
    mask = rng.random(bits.shape) < 0.5
    bits[mask] = rng.choice(salt, size=int(mask.sum()))
    return bits.view(np.float32)


# (k, n): the two-stage path starts at n = 4·k·128 columns
_TOPK_CASES = {
    **{
        f"ties-k{k}-n{n}": _row_case(lambda k=k, n=n: _tied_rows((8, n), seed=k + n), k)
        for k in (1, 3, 5, 10, 100)
        for n in (4 * k * 128 - 1, 4 * k * 128 + 77)
    },
    "ties-k1-n100000-twice-grouped": _row_case(lambda: _tied_rows((8, 100_000), seed=1), 1),
    "ties-k10-n300001-twice-grouped": _row_case(lambda: _tied_rows((8, 300_001), seed=2), 10),
    "ties-batch-axis": _row_case(lambda: _tied_rows((2, 3, 9_000), seed=3), 5),
    "ties-one-row": _row_case(lambda: _tied_rows((9_000,), seed=4), 3),
    "ties-narrow-groups": _row_case(lambda: _tied_rows((8, 5_000), seed=5), 3, block=8),
    "ties-all-negative": _row_case(lambda: _tied_rows((8, 7_000), seed=6, offset=-40.0), 10),
    "all-equal": _row_case(lambda: np.zeros((8, 7_000), np.float32), 10),
    "signed-zeros-and-nans": _row_case(_bit_pattern_rows, 5),
    **{f"search-program-k{k}": _search_case(k) for k in (3, 5, 10)},
}


@pytest.mark.parametrize("case", list(_TOPK_CASES))
def test_blocked_topk_is_lax_topk_bit_for_bit(case):
    """The two-stage selection returns ``lax.top_k`` over the whole row:
    the same value bits and the same ids, lowest id first among ties."""
    vals, ids, scores, k = _TOPK_CASES[case]()
    ref_vals, ref_ids = jax.lax.top_k(scores, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    np.testing.assert_array_equal(
        np.asarray(vals).view(np.int32), np.asarray(ref_vals).view(np.int32)
    )


def test_merge_topk():
    va, ia = jnp.array([9.0, 5.0]), jnp.array([0, 1])
    vb, ib = jnp.array([7.0, 6.0]), jnp.array([2, 3])
    v, i = merge_topk(va, ia, vb, ib, 3)
    np.testing.assert_allclose(np.asarray(v), [9.0, 7.0, 6.0])
    np.testing.assert_array_equal(np.asarray(i), [0, 2, 3])


# --------------------------------------------------------------------------- #
# BM25                                                                         #
# --------------------------------------------------------------------------- #
def test_bm25_retrieves_lexical_match():
    ps = line_passages(corpus_document())
    bm = BM25Index(ps)
    scores, ids = bm.search("FAISS approximate nearest neighbor", k=3)
    assert ps[int(ids[0])].text == BENCHMARK_CORPUS[9]
    assert scores[0] > 0


def test_bm25_empty_query_scores_zero():
    bm = BM25Index(line_passages(corpus_document()))
    assert bm.score("").max() == 0.0
    assert bm.score("zzzzqqqq xylophone").max() == 0.0


def test_bm25_idf_downweights_common_terms():
    # "retrieval" appears in many lines; "municipal" in exactly one.
    bm = BM25Index(line_passages(corpus_document()))
    s_rare = bm.score("municipal")
    s_common = bm.score("retrieval")
    assert s_rare.max() > s_common.max()


# --------------------------------------------------------------------------- #
# IVF                                                                          #
# --------------------------------------------------------------------------- #
def test_kmeans_assigns_all_points():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(200, 16)).astype(np.float32))
    from repro.retrieval import l2_normalize

    cent, assign = kmeans(l2_normalize(x), 8, n_iters=5)
    assert cent.shape == (8, 16)
    assert assign.shape == (200,)
    assert int(assign.max()) < 8


def test_ivf_full_probe_matches_exact():
    rng = np.random.default_rng(1)
    emb = jnp.asarray(rng.normal(size=(256, 32)).astype(np.float32))
    ivf = IVFIndex.build(emb, n_clusters=8, key=jax.random.PRNGKey(0))
    q = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    # probing ALL clusters must equal exact search
    recall = ivf.recall_vs_exact(q, k=10, n_probe=8)
    assert recall == 1.0


def test_ivf_partial_probe_reasonable_recall():
    rng = np.random.default_rng(2)
    emb = jnp.asarray(rng.normal(size=(512, 32)).astype(np.float32))
    ivf = IVFIndex.build(emb, n_clusters=16, key=jax.random.PRNGKey(1))
    q = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    recall = ivf.recall_vs_exact(q, k=5, n_probe=4)
    assert recall >= 0.5  # random data: 4/16 probes still find most neighbors


# --------------------------------------------------------------------------- #
# Hybrid fusion                                                                #
# --------------------------------------------------------------------------- #
def test_rrf_fuse_prefers_doubly_ranked():
    a = (np.array([3.0, 2.0, 1.0]), np.array([10, 11, 12]))
    b = (np.array([9.0, 8.0, 1.0]), np.array([10, 13, 14]))
    scores, ids = rrf_fuse([a, b], k=3)
    assert ids[0] == 10  # appears top-ranked in both lists
    assert scores[0] > scores[1]


def test_weighted_fuse_extremes():
    d = (np.array([1.0, 0.5]), np.array([0, 1]))
    s = (np.array([0.5, 1.0]), np.array([0, 1]))
    _, ids_dense = weighted_fuse(d, s, k=1, w_dense=1.0)
    _, ids_sparse = weighted_fuse(d, s, k=1, w_dense=0.0)
    assert ids_dense[0] == 0 and ids_sparse[0] == 1


def test_hybrid_retriever_end_to_end():
    ps = line_passages(corpus_document())
    dense, _ = DenseIndex.build(ps, EMB)
    hybrid = HybridRetriever(dense, BM25Index(ps), EMB, fusion="rrf")
    r = hybrid.search("hybrid dense sparse retrieval BM25", k=3)
    texts = " ".join(ps[int(i)].text for i in r.passage_ids)
    assert "BM25" in texts
    with pytest.raises(ValueError):
        HybridRetriever(dense, BM25Index(ps), EMB, fusion="bogus")


# --------------------------------------------------------------------------- #
# Distributed search (shard_map on CPU devices)                                #
# --------------------------------------------------------------------------- #
def test_sharded_search_matches_exact_single_device():
    # 1-device mesh degenerate case still exercises the shard_map path.
    from repro.distributed import make_mesh

    idx, _, _ = _paper_index()
    mesh = make_mesh((1,), ("data",))
    fn, n_shards = idx.sharded_search_fn(mesh, k=5, shard_axes=("data",))
    assert n_shards == 1
    qs = EMB.embed(list(BENCHMARK_QUERIES[:4]))
    v, i = fn(idx.embeddings, qs)
    ev, ei = idx.search_batch(qs, k=5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ev), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
