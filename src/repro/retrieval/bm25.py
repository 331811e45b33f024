"""BM25 sparse scoring (Robertson & Zaragoza) — the hybrid-fusion partner.

The paper preserves "BM25-compatible tokenization for future hybrid fusion"
(§II.B); we implement the scorer itself so hybrid.py can fuse it with dense
scores. Host-side builds a hashed term→postings structure; JAX has no CSR,
so postings are a flat COO edge list and the scoring scatter is
``jax.ops.segment_sum`` (kernel_taxonomy §B.11: this IS the system, not a
stub).

Two scoring paths:

* :meth:`BM25Index.search_batch` — the serving path. Queries run in fixed
  ``Q_BLOCK`` chunks through *cached jit closures* keyed on
  ``(k, padded edge count)``: each chunk's matching postings concatenate
  into one edge list, padded to a power-of-two bucket (pads route to a
  dummy segment, so padding adds exact zeros and never retraces), and one
  fused device program does segment-sum scoring into a
  ``(Q_BLOCK, n_passages)`` block plus an on-device ``lax.top_k``. The
  fixed shapes make every row bit-identical across batch sizes — the same
  discipline as ``DenseIndex`` — and eliminate the per-batch-shape XLA
  compile churn that made the extended catalog ~15× slower than dense.
* :meth:`score_batch` — the dense ``(nq, n_passages)`` score matrix, kept
  as the differential-testing oracle and for callers that want full rows.

Empty rows are explicit: a slot with no matching passage comes back as the
sentinel ``(id=-1, score=0.0)`` (real BM25 matches score strictly
positive), so downstream consumers can tell "no lexical hit" from "passage
0 scored 0" — the :class:`~repro.retrieval.backend.RetrievalBackend`
sentinel contract.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.retrieval.chunking import Passage
from repro.retrieval.embedder import _stable_hash
from repro.retrieval.tokenizer import terms

# Edge lists pad to the next power-of-two bucket, floored here, so the
# number of distinct compiled closures stays logarithmic in the largest
# batch's posting count (compare Q_BLOCK in retrieval/index.py).
_MIN_EDGE_BUCKET = 64


def _edge_bucket(n: int) -> int:
    """Next power-of-two edge-list capacity >= n (floored)."""
    cap = _MIN_EDGE_BUCKET
    while cap < n:
        cap <<= 1
    return cap


@dataclasses.dataclass(frozen=True)
class BM25Params:
    k1: float = 1.2
    b: float = 0.75
    vocab_hash_bits: int = 18  # 262144 hashed term slots


class BM25Index:
    """Hashed-vocabulary BM25 with a fused segment-sum + top-k device path.

    Postings are stored as flat COO arrays (term_slot, passage_id, tf):
    scoring a query gathers the matching postings by slot and segment-sums
    per-passage contributions.
    """

    def __init__(self, passages: Sequence[Passage], params: BM25Params | None = None):
        # Default to None and construct per instance: a shared default
        # instance in the signature would alias every index built without
        # explicit params onto one object (harmless while BM25Params stays
        # frozen, a footgun the moment it grows mutable state).
        self.params = params if params is not None else BM25Params()
        self.n_passages = len(passages)
        self._slots = 1 << self.params.vocab_hash_bits

        doc_lens = np.zeros((self.n_passages,), np.float32)
        post_term: list[int] = []
        post_doc: list[int] = []
        post_tf: list[float] = []
        df: dict[int, int] = {}
        for pid, p in enumerate(passages):
            ts = terms(p.text, remove_stopwords=True)
            doc_lens[pid] = len(ts)
            counts: dict[int, int] = {}
            for t in ts:
                slot = _stable_hash(t, "bm25") % self._slots
                counts[slot] = counts.get(slot, 0) + 1
            for slot, tf in counts.items():
                post_term.append(slot)
                post_doc.append(pid)
                post_tf.append(float(tf))
                df[slot] = df.get(slot, 0) + 1

        self.doc_lens = jnp.asarray(doc_lens)
        self.avgdl = float(doc_lens.mean()) if self.n_passages else 0.0
        self.post_term = np.asarray(post_term, np.int64)
        order = np.argsort(self.post_term, kind="stable")
        # sort postings by term slot for fast searchsorted gather; keep the
        # doc column on host too (the batched path computes segment ids there)
        self.post_term = self.post_term[order]
        self._post_doc_np = np.asarray(post_doc, np.int32)[order]
        self.post_doc = jnp.asarray(self._post_doc_np)
        self.post_tf = jnp.asarray(np.asarray(post_tf, np.float32)[order])
        # idf per posting (precomputed — slot idf is static)
        n = max(self.n_passages, 1)
        idf = np.array(
            [np.log(1.0 + (n - df[t] + 0.5) / (df[t] + 0.5)) for t in post_term], np.float32
        )
        self.post_idf = jnp.asarray(idf[order])
        # Per-posting BM25 contribution, precomputed: the saturated-tf term
        # depends only on (tf, idf, doc_len, avgdl) — never on the query —
        # so the whole scoring arithmetic happens once at build time and
        # every search is a pure gather + segment-sum over these statics.
        # (Also what makes the oracle and device paths bit-identical: XLA
        # fuses a jitted mul/div chain differently from eager dispatch,
        # but a precomputed value has no chain left to fuse.)
        k1, b = self.params.k1, self.params.b
        tf_np = np.asarray(post_tf, np.float32)[order]
        idf_np = idf[order]
        dl_np = doc_lens[self._post_doc_np]
        denom = tf_np + k1 * (1.0 - b + b * dl_np / max(self.avgdl, 1e-9))
        self._post_contrib_np = (idf_np * tf_np * (k1 + 1.0) / denom).astype(np.float32)
        self.post_contrib = jnp.asarray(self._post_contrib_np)
        # (k, edge bucket) → jit-compiled fixed-shape search closure
        self._fn_cache: dict = {}

    def _postings_for(self, query: str) -> np.ndarray:
        """Indices of this query's matching postings (sorted-slot ranges)."""
        q_slots = sorted(
            {_stable_hash(t, "bm25") % self._slots for t in terms(query, remove_stopwords=True)}
        )
        if not q_slots:
            return np.array([], np.int64)
        # host-side postings range lookup (binary search over sorted slots)
        lo = np.searchsorted(self.post_term, q_slots, side="left")
        hi = np.searchsorted(self.post_term, q_slots, side="right")
        return np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])

    def score(self, query: str) -> np.ndarray:
        """BM25 scores for all passages, shape (n_passages,)."""
        return self.score_batch([query])[0]

    def score_batch(self, queries: Sequence[str]) -> np.ndarray:
        """BM25 scores for a query batch, shape (n_queries, n_passages).

        One fused device pass for the whole batch: every query's matching
        postings concatenate into a single edge list whose segment id is
        ``row * n_passages + doc``, so a lone ``segment_sum`` scatters all
        (query, passage) contributions at once — the batched mirror of the
        single-query path, bit-identical per row regardless of batch shape
        (each row's postings are disjoint segments). This is the dense
        oracle path; the serving hot path is :meth:`search_batch`.
        """
        nq = len(queries)
        if nq == 0 or self.n_passages == 0:
            return np.zeros((nq, self.n_passages), np.float32)
        sels = [self._postings_for(q) for q in queries]
        total = sum(s.size for s in sels)
        if total == 0:
            return np.zeros((nq, self.n_passages), np.float32)
        sel = np.concatenate([s for s in sels if s.size])
        rows = np.concatenate(
            [np.full((s.size,), r, np.int64) for r, s in enumerate(sels) if s.size]
        )
        seg = rows * self.n_passages + self._post_doc_np[sel]
        out = self._score_postings(
            jnp.asarray(sel.astype(np.int32)),
            jnp.asarray(seg.astype(np.int32)),
            nq * self.n_passages,
        )
        return np.asarray(out).reshape(nq, self.n_passages)

    def _score_postings(
        self, sel: jnp.ndarray, seg: jnp.ndarray, num_segments: int
    ) -> jnp.ndarray:
        return jax.ops.segment_sum(
            self.post_contrib[sel], seg, num_segments=num_segments
        )

    # -- device search path ----------------------------------------------------
    def _search_fn(self, k: int, e_pad: int):
        """Cached jit program ``(post_contrib, sel (E,), seg (E,)) →
        ((Q_BLOCK, k), (Q_BLOCK, k))`` — segment-sum scoring into a fixed
        ``(Q_BLOCK, n_passages)`` block, on-device ``lax.top_k``, sentinel
        masking. Compiled once per (k, edge bucket); every shape in the
        program is static, so batch sizes never retrace.

        Pad edges carry ``seg == Q_BLOCK * n_passages`` — one dummy segment
        past the real block — so their contributions land nowhere and real
        segments sum exactly the same entries, in the same order, as the
        unpadded edge list (bit-identity of the padding).
        """
        from repro.retrieval.index import Q_BLOCK

        key = (k, e_pad)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        n = self.n_passages
        num_segments = Q_BLOCK * n + 1  # + the pad dummy segment

        # the posting contributions are an argument, never a captured
        # constant that would be compiled into the program
        def core(post_contrib: jax.Array, sel: jax.Array, seg: jax.Array):
            flat = jax.ops.segment_sum(
                post_contrib[sel], seg, num_segments=num_segments
            )
            scores = flat[: Q_BLOCK * n].reshape(Q_BLOCK, n)
            v, i = jax.lax.top_k(scores, k)
            # sentinel semantics: a real BM25 match scores strictly
            # positive, so score <= 0 ⇔ no matching passage in this slot
            hit = v > 0.0
            return jnp.where(hit, v, 0.0), jnp.where(hit, i, -1)

        fn = self._fn_cache[key] = jax.jit(core)
        return fn

    def search(self, query: str, k: int) -> tuple[np.ndarray, np.ndarray]:
        scores, ids = self.search_batch([query], k)
        return scores[0], ids[0]

    def search_batch(
        self, queries: Sequence[str], k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """(n,) query strings → (scores (n, k'), ids (n, k')), descending
        per row with stable passage-id tie-breaks; ``k' = min(k, corpus)``.

        Slots with no matching passage are the sentinel ``(-1, 0.0)``; a
        query with no matching terms comes back as a full sentinel row.
        Queries run in fixed ``Q_BLOCK`` chunks through the cached device
        closures (:meth:`_search_fn`), so each row is bit-identical whether
        it arrives alone or inside any batch.
        """
        from repro.retrieval.index import Q_BLOCK

        k = min(k, self.n_passages)
        nq = len(queries)
        if nq == 0 or k == 0:
            return np.zeros((nq, k), np.float32), np.zeros((nq, k), np.int32)
        if self.post_term.size == 0:
            # corpus with no postings at all: every row is empty
            return (
                np.zeros((nq, k), np.float32),
                np.full((nq, k), -1, np.int32),
            )
        sels = [self._postings_for(q) for q in queries]
        out_scores = np.empty((nq, k), np.float32)
        out_ids = np.empty((nq, k), np.int32)
        dummy = Q_BLOCK * self.n_passages
        for s in range(0, nq, Q_BLOCK):
            chunk = sels[s : s + Q_BLOCK]
            total = sum(c.size for c in chunk)
            e_pad = _edge_bucket(total)
            sel = np.zeros((e_pad,), np.int32)
            seg = np.full((e_pad,), dummy, np.int32)
            off = 0
            for r, c in enumerate(chunk):
                if c.size:
                    sel[off : off + c.size] = c
                    seg[off : off + c.size] = r * self.n_passages + self._post_doc_np[c]
                    off += c.size
            fn = self._search_fn(k, e_pad)
            v, i = fn(self.post_contrib, jnp.asarray(sel), jnp.asarray(seg))
            rows = len(chunk)
            out_scores[s : s + rows] = np.asarray(v, np.float32)[:rows]
            out_ids[s : s + rows] = np.asarray(i, np.int32)[:rows]
        return out_scores, out_ids

    # -- sharding --------------------------------------------------------------
    def shard(self, n_shards: int) -> "list[BM25Index]":
        """Split into ``n_shards`` contiguous-range views with **replicated
        global statistics** — the sparse-sharding seam.

        Each view keeps the *corpus-global* idf (per-posting, precomputed
        from global document frequencies) and the global ``avgdl``, so a
        (query, passage) pair's BM25 contribution is bitwise identical to
        the unsharded index — which is what makes the per-shard top-k merge
        (:class:`~repro.retrieval.sharded.ShardedBackend`) bit-identical to
        unsharded search. Postings are filtered per range with doc ids
        re-based; slot order (and therefore per-segment summation order) is
        preserved by the filter.
        """
        from repro.retrieval.sharded import shard_bounds

        post_tf = np.asarray(self.post_tf)
        post_idf = np.asarray(self.post_idf)
        doc_lens = np.asarray(self.doc_lens)
        views: list[BM25Index] = []
        for start, stop in shard_bounds(self.n_passages, n_shards):
            v = object.__new__(BM25Index)
            v.params = self.params
            v.n_passages = stop - start
            v._slots = self._slots
            keep = (self._post_doc_np >= start) & (self._post_doc_np < stop)
            v.post_term = self.post_term[keep]
            v._post_doc_np = (self._post_doc_np[keep] - start).astype(np.int32)
            v.post_doc = jnp.asarray(v._post_doc_np)
            v.post_tf = jnp.asarray(post_tf[keep])
            v.post_idf = jnp.asarray(post_idf[keep])  # global idf, replicated
            v.doc_lens = jnp.asarray(doc_lens[start:stop])
            v.avgdl = self.avgdl  # global avgdl, replicated
            # global precomputed contributions: the shard copies the exact
            # float32 values, so per-(query, passage) scores cannot drift
            v._post_contrib_np = self._post_contrib_np[keep]
            v.post_contrib = jnp.asarray(v._post_contrib_np)
            v._fn_cache = {}
            views.append(v)
        return views
