"""Pluggable retrieval backends behind one batched protocol.

The paper's strategy bundles couple a retrieval depth with a generation
profile; "Fast or Better?" (Su et al., 2025) and RAGO (Jiang et al., 2025)
show the *retrieval method* is an equally load-bearing axis of the
cost-accuracy tradeoff. This module is the seam that makes the method
pluggable: every retriever in the repo — exact dense MIPS, IVF approximate,
BM25 lexical, hybrid fusion — adapts to one :class:`RetrievalBackend`
protocol with a single batched entry point::

    search_batch(queries, query_vecs, k) -> (scores (n, k), ids (n, k))

plus a static :class:`BackendCost` descriptor (per-query FLOP / latency /
recall priors) that the routing layer consumes, so the bundle catalog can
express (backend × depth × generation) operating points and the router can
discriminate between them without executing anything.

Contracts every adapter honors:

* ``queries`` are the raw query strings and ``query_vecs`` the embedded
  ``(n, d)`` matrix; an adapter reads whichever representation it needs
  (``requires_query_vecs`` tells the serving layer whether to spend the
  embed call at all — BM25 never does).
* ``nested_topk`` (optional, false when absent) declares that a backend's
  top-k rows nest: for k' < k, the top-k' row is the first k' entries of
  the top-k row, bit for bit. The serving stages then search such a
  backend once, at the deepest k the catalog routes to it, and serve every
  shallower depth from that row's prefix.
* Rows come back descending by fused/backend score, ids are passage ids
  into the shared corpus, and ``k`` is clamped to the corpus size.
* Results are deterministic pure functions of (corpus, query, k): the
  serving pipeline's exact-replay parity — drained streaming runs are
  bit-identical to ``answer_batch`` under mixed-backend catalogs — depends
  on it, and so does running searches on worker threads.

``DenseBackend`` wraps the jit/pallas :class:`DenseIndex` path unchanged
(bit-identical to calling the index directly — the paper catalog's records
cannot move). ``IVFBackend`` exposes ``n_probe``; ``BM25Backend`` and
``HybridBackend`` wrap the batched lexical/fused paths.
"""

from __future__ import annotations

import dataclasses
from typing import Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.retrieval.bm25 import BM25Index
from repro.retrieval.chunking import Passage
from repro.retrieval.embedder import Embedder
from repro.retrieval.hybrid import HybridRetriever
from repro.retrieval.index import DenseIndex
from repro.retrieval.ivf import IVFIndex


@dataclasses.dataclass(frozen=True)
class BackendCost:
    """Static per-query cost/quality priors for one retrieval backend.

    ``latency_scale`` multiplies the latency model's retrieve-stage time
    (1.0 = exact dense MIPS over the full corpus — the calibration anchor).
    ``recall_prior`` is the expected recall@k against exact retrieval; the
    utility function multiplies it into the bundle's quality prior, which is
    how routing discriminates a cheap approximate bundle from an exact one
    *before* executing either. ``flops_per_item`` is scoring FLOPs per
    corpus item per query (descriptive telemetry; roofline cells read it).
    """

    latency_scale: float = 1.0
    recall_prior: float = 1.0
    flops_per_item: float = 0.0

    def __post_init__(self):
        if self.latency_scale <= 0:
            raise ValueError(f"latency_scale must be > 0, got {self.latency_scale}")
        if not (0.0 < self.recall_prior <= 1.0):
            raise ValueError(f"recall_prior must be in (0, 1], got {self.recall_prior}")

    def flops_per_query(self, corpus_size: int) -> float:
        """Total scoring FLOPs one query spends over a corpus of this size."""
        return self.flops_per_item * corpus_size


# Catalog-level defaults by backend *name*: what the routing layer assumes
# when it only has a bundle's ``backend`` string (no live instance), e.g.
# inside ``BundleCatalog.as_arrays``. Adapter instances refine these from
# their actual parameters (corpus size, dim, n_probe). An unknown name maps
# to the neutral descriptor, so future backends compose without edits here.
DEFAULT_BACKEND_COSTS: dict[str, BackendCost] = {
    # exact MIPS: 2*d FLOPs per item at the reference d=256
    "dense": BackendCost(latency_scale=1.0, recall_prior=1.0, flops_per_item=512.0),
    # probes a fraction of the corpus; priors match the default n_probe=2/4
    "ivf": BackendCost(latency_scale=0.55, recall_prior=0.81, flops_per_item=256.0),
    # hashed postings: a handful of ops per item, no embed stage at all
    "bm25": BackendCost(latency_scale=0.25, recall_prior=0.62, flops_per_item=8.0),
    # dense + sparse + rank fusion: costs the sum, recalls the union
    "hybrid": BackendCost(latency_scale=1.35, recall_prior=1.0, flops_per_item=520.0),
}

_NEUTRAL_COST = BackendCost()


def backend_cost(name: str) -> BackendCost:
    """Static cost descriptor for a backend name (neutral when unknown)."""
    return DEFAULT_BACKEND_COSTS.get(name, _NEUTRAL_COST)


@runtime_checkable
class RetrievalBackend(Protocol):
    """One batched retrieval method the serving layer can route to."""

    name: str
    cost: BackendCost
    requires_query_vecs: bool

    @property
    def size(self) -> int:
        """Corpus passages indexed."""
        ...

    def search_batch(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(scores (n, k'), ids (n, k')), descending per row.

        Signature/dtype contract (asserted for every backend — wrapped or
        bare — by the shared conformance test in
        tests/test_backend_contract.py):

        * ``scores`` are ``float32`` and ``ids`` are ``int32`` (as numpy
          arrays or jnp arrays that convert losslessly via ``np.asarray``);
          both are ``(n, k')`` with one row per input query, in input order.
        * Each row is sorted by score **descending**; ties resolve to the
          lowest passage id (the total order every top-k primitive in the
          repo — ``lax.top_k``, ``blocked_topk``, ``merge_topk``,
          ``distributed_topk`` — implements, which is what makes sharded/
          cached/resilient wrappers bit-identical to the bare backend).
        * ``k' = min(k, corpus size)`` for the exact backends; an
          approximate backend may narrow further when its candidate pool is
          smaller (IVF: ``k' = min(k, n_probe × bucket_capacity)``). Rows
          never contain out-of-corpus ids, and consumers (the serving
          ``assemble`` stage) handle any row width.
        * One sanctioned exception to the descending clause: a backend may
          set ``scores_are_ranking = False`` (hybrid RRF does — rows are
          ranked by fused reciprocal rank but report the dense cosine per
          id so confidences stay comparable across backends). Row *order*
          is then the contract; reported scores need only be finite.
        * **Empty-slot sentinels**: a backend whose candidate pool can run
          dry mid-row (BM25 — a query may lexically match fewer than ``k``
          passages, or none) fills the unmatched tail with the sentinel
          pair ``(id=-1, score=0.0)`` instead of fabricating passage ids.
          Sentinels always form a contiguous row *suffix* (real hits
          first), their score is exactly ``0.0``, and the descending /
          unique-ids clauses apply to the real-hit prefix only. Consumers
          must treat ``id == -1`` as "no passage" — the serving
          ``assemble`` stage drops sentinel slots before resolving
          payloads, and ``ShardedBackend`` merges them last and never
          offsets them into real ids."""
        ...

    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Resolve returned passage ids to their text payloads."""
        ...


def is_nested(backend: object) -> bool:
    """Whether ``backend`` declares ``nested_topk`` (false when absent)."""
    return bool(getattr(backend, "nested_topk", False))


class DenseBackend:
    """Exact MIPS through the jit/pallas :class:`DenseIndex` path.

    Pure delegation: scores/ids are bit-identical to calling
    ``index.search_batch`` directly, so wiring the paper catalog through the
    backend seam cannot move a record.
    """

    name = "dense"
    requires_query_vecs = True
    # both scorers order ties by the lowest id, so top-k' is top-k's prefix
    nested_topk = True

    def __init__(self, index: DenseIndex, *, scorer: str = "blocked", interpret: bool = False):
        self.index = index
        self.scorer = scorer
        self.interpret = interpret
        self.cost = BackendCost(
            latency_scale=1.0, recall_prior=1.0, flops_per_item=2.0 * index.dim
        )

    @property
    def size(self) -> int:
        """Corpus passages indexed."""
        return self.index.size

    def search_batch(self, queries, query_vecs, k):
        """Exact MIPS over the full corpus (pure index delegation)."""
        return self.index.search_batch(
            query_vecs, k, scorer=self.scorer, interpret=self.interpret
        )

    def get_passages(self, ids) -> list[Passage]:
        """Resolve passage ids through the wrapped index."""
        return self.index.get_passages(ids)


class IVFBackend:
    """Probed approximate search over an :class:`IVFIndex`.

    ``n_probe`` is the cost/recall knob: the descriptor's latency scale and
    recall prior are derived from the probed-cluster fraction (recall is
    monotonic in ``n_probe`` and reaches 1.0 at a full probe — pinned by the
    property tests). ``IVFIndex.recall_vs_exact`` measures the real recall
    when a deployment wants to calibrate the prior.
    """

    name = "ivf"
    requires_query_vecs = True

    def __init__(
        self,
        ivf: IVFIndex,
        passages: Sequence[Passage] | None = None,
        *,
        n_probe: int = 4,
        truncate_nonfinite: bool = True,
    ):
        if n_probe < 1:
            raise ValueError(f"n_probe must be >= 1, got {n_probe}")
        self.ivf = ivf
        self.n_probe = min(n_probe, ivf.n_clusters)
        self.passages = list(passages) if passages is not None else None
        # ShardedBackend.from_ivf sets truncate_nonfinite=False on its
        # per-shard adapters: truncating each shard's row to its own finite
        # prefix before the merge would discard real candidates another
        # shard can't supply — the sharded wrapper truncates once, globally,
        # after the merge instead.
        self.truncate_nonfinite = bool(truncate_nonfinite)
        frac = self.n_probe / ivf.n_clusters
        dim = int(ivf.embeddings.shape[1])
        self.cost = BackendCost(
            # centroid scoring + probed-bucket scoring, vs full exact MIPS
            latency_scale=max(0.1 + 0.9 * frac, 1e-3),
            # concave prior: most neighbors live in the nearest clusters;
            # exact at a full probe
            recall_prior=min(1.0, frac**0.3),
            flops_per_item=2.0 * dim * frac,
        )

    @property
    def size(self) -> int:
        """Corpus passages indexed."""
        return int(self.ivf.embeddings.shape[0])

    def search_batch(self, queries, query_vecs, k):
        """Probed approximate search over the ``n_probe`` nearest clusters."""
        # Rows may come back narrower than k when the probed candidate pool
        # is smaller (k' = min(k, n_probe × bucket_capacity)): with few
        # clusters and a small corpus an ivf bundle's top_k can exceed what
        # n_probe buckets hold. Size n_probe so n_probe × capacity >= k to
        # guarantee full-width rows (the extended-catalog default does).
        k = min(k, self.size)
        scores, ids = self.ivf.search_batch(query_vecs, k, n_probe=self.n_probe)
        scores = np.asarray(scores, np.float32)
        ids = np.asarray(ids, np.int32)
        # Degenerate probes (fewer valid candidates than k) pad with -inf
        # in the IVF kernel. Rows narrow to the widest all-finite prefix
        # instead of repeating the best hit: duplicated ids and a re-rising
        # score tail would break the protocol's descending/unique-ids
        # contract (k' <= k is first-class for approximate backends).
        if self.truncate_nonfinite:
            bad = ~np.isfinite(scores)
            if bad.any():
                width = int((~bad).sum(axis=1).min())
                scores, ids = scores[:, :width], ids[:, :width]
        return scores, ids

    def get_passages(self, ids) -> list[Passage]:
        """Resolve passage ids against the stored payloads."""
        if self.passages is None:
            raise ValueError("IVFBackend built without passage payloads")
        return [self.passages[int(i)] for i in ids]


class BM25Backend:
    """Batched lexical scoring — the only backend that never embeds.

    Scores are BM25 values (unbounded, not cosine), so the low-confidence
    guardrail threshold is *not* comparable across backends; bundles on this
    backend should either disable the guardrail or use a BM25-scale
    threshold (docs/retrieval.md).
    """

    name = "bm25"
    requires_query_vecs = False

    def __init__(self, bm25: BM25Index, passages: Sequence[Passage]):
        self.bm25 = bm25
        self.passages = list(passages)
        self.cost = BackendCost(latency_scale=0.25, recall_prior=0.62, flops_per_item=8.0)

    @property
    def size(self) -> int:
        """Corpus passages indexed."""
        return self.bm25.n_passages

    def search_batch(self, queries, query_vecs, k):
        """Batched lexical scoring (query vectors are ignored)."""
        return self.bm25.search_batch(queries, k)

    def get_passages(self, ids) -> list[Passage]:
        """Resolve passage ids against the stored payloads."""
        return [self.passages[int(i)] for i in ids]


class HybridBackend:
    """Dense + BM25 rank fusion through :class:`HybridRetriever`.

    Takes the already-embedded query vectors from the serving layer (the
    engine's query-vector cache), so the dense side never re-embeds.
    """

    name = "hybrid"
    requires_query_vecs = True

    def __init__(self, hybrid: HybridRetriever):
        self.hybrid = hybrid
        # RRF rows are ranked by fused reciprocal rank but *report* the dense
        # cosine of each id (confidence comparability across backends), so
        # the reported score vector is not monotone — the one sanctioned
        # exception to the protocol's descending-scores clause.
        self.scores_are_ranking = hybrid.fusion != "rrf"
        dim = hybrid.dense.dim
        self.cost = BackendCost(
            latency_scale=1.35, recall_prior=1.0, flops_per_item=2.0 * dim + 8.0
        )

    @property
    def size(self) -> int:
        """Corpus passages indexed."""
        return self.hybrid.dense.size

    def search_batch(self, queries, query_vecs, k):
        """Fused dense + BM25 search (reuses the given query vectors)."""
        return self.hybrid.search_batch(queries, k, query_vecs=query_vecs)

    def get_passages(self, ids) -> list[Passage]:
        """Resolve passage ids through the dense side's index."""
        return self.hybrid.dense.get_passages(ids)


def make_backends(
    index: DenseIndex,
    passages: Sequence[Passage],
    embedder: Embedder,
    *,
    names: Sequence[str] = ("dense",),
    n_clusters: int = 4,
    n_probe: int = 2,
    fusion: str = "rrf",
    seed: int = 0,
) -> dict[str, "RetrievalBackend"]:
    """Build the requested backends over one shared corpus.

    The dense index/embeddings are shared (IVF clusters the same vectors,
    hybrid fuses against the same index), and BM25 postings are built once
    even when both ``bm25`` and ``hybrid`` are requested. Deterministic:
    IVF k-means is seeded, so repeated builds route identically.
    """
    backends: dict[str, RetrievalBackend] = {}
    bm25: BM25Index | None = None

    def _bm25() -> BM25Index:
        nonlocal bm25
        if bm25 is None:
            bm25 = BM25Index(passages)
        return bm25

    for name in dict.fromkeys(names):  # unique, order-preserving
        if name == "dense":
            backends[name] = DenseBackend(index)
        elif name == "bm25":
            backends[name] = BM25Backend(_bm25(), passages)
        elif name == "ivf":
            ivf = IVFIndex.build(
                index.embeddings,
                n_clusters=min(n_clusters, index.size),
                key=jax.random.PRNGKey(seed),
            )
            backends[name] = IVFBackend(ivf, passages, n_probe=n_probe)
        elif name == "hybrid":
            backends[name] = HybridBackend(
                HybridRetriever(index, _bm25(), embedder, fusion=fusion)
            )
        else:
            raise ValueError(
                f"unknown backend {name!r}; make_backends builds "
                "{'dense', 'ivf', 'bm25', 'hybrid'} — pass custom backends "
                "to RAGEngine directly"
            )
    return backends
