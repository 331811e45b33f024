"""Query-result caching for retrieval backends — the repeat-traffic fast path.

RAGO (Jiang et al., 2025) identifies retrieval caching as a dominant lever
for RAG serving throughput: production query streams are heavily repetitive
(reformulations, paging, trending topics), and a cache hit turns a corpus
scan into a dictionary lookup. :class:`CachedBackend` is the decorator that
brings that lever to every retriever in the repo: it wraps any
:class:`~repro.retrieval.backend.RetrievalBackend` behind the same batched
protocol, so bundles, the serving stages, and the CLI compose with it
without knowing it exists.

Design contracts:

* **Exact keys only.** A row is served from cache only when its key — the
  raw bytes of the embedded query vector *and* the query string for vector
  backends (hybrid's BM25 half reads the text), the query string for
  lexical ones — plus the requested ``k`` match exactly. No
  near-duplicate matching: a hit is *bit-identical* to the inner backend's
  answer by construction, which is what keeps cached serving inside every
  parity guarantee the repo pins (drained streaming ≡ ``answer_batch`` ≡
  the sequential loop).
* **Deterministic eviction.** The cache is a bounded LRU over insertion/
  touch order. Single-threaded runs therefore produce bit-stable
  hit/miss/eviction counters — the property the CI gate's band-0 cache
  cell in ``BENCH_serving.json`` relies on. (Under concurrent micro-batches
  the *counters* may interleave differently run to run; the *results* never
  change, because a miss just recomputes the same pure function.)
* **Observable.** Per-call deltas flow through
  :meth:`CachedBackend.search_batch_stats` into the retrieve stage's
  artifact, accumulate in :class:`~repro.serving.stages.StagePipeline`, and
  surface as ``StreamResult.summary()["backend_cache"]``; cumulative totals
  are always available via :meth:`CachedBackend.stats`.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from repro.retrieval.backend import BackendCost, RetrievalBackend, is_nested
from repro.retrieval.chunking import Passage


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Hit/miss/eviction counters for one :class:`CachedBackend`.

    ``hits + misses`` equals the number of query rows served; ``evictions``
    counts entries pushed out of the LRU by capacity pressure. Instances are
    immutable snapshots — per-call deltas and cumulative totals use the same
    type.
    """

    hits: int = 0
    misses: int = 0
    evictions: int = 0

    def __add__(self, other: "CacheStats") -> "CacheStats":
        """Component-wise sum — accumulating per-call deltas into totals."""
        return CacheStats(
            self.hits + other.hits,
            self.misses + other.misses,
            self.evictions + other.evictions,
        )

    def as_dict(self) -> dict[str, int]:
        """Plain-dict view for JSON artifacts and run summaries."""
        return {"hits": self.hits, "misses": self.misses, "evictions": self.evictions}


class CachedBackend:
    """Exact query-result LRU wrapped around any retrieval backend.

    Drop-in: ``name`` / ``cost`` / ``requires_query_vecs`` delegate to the
    inner backend, so a bundle that routes to ``"dense"`` routes identically
    to a cached dense backend. ``capacity`` bounds the number of cached
    ``(query, k)`` rows; eviction is strict LRU (deterministic — see the
    module docstring).
    """

    def __init__(self, inner: RetrievalBackend, *, capacity: int = 1024):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.inner = inner
        self.capacity = int(capacity)
        self._lru: OrderedDict[tuple, tuple[np.ndarray, np.ndarray]] = OrderedDict()
        self._lock = threading.Lock()
        self._stats = CacheStats()

    # -- protocol surface (delegation) --------------------------------------
    @property
    def name(self) -> str:
        """The inner backend's routing name — cache wrapping is invisible."""
        return self.inner.name

    @property
    def cost(self) -> BackendCost:
        """The inner backend's static cost descriptor (priors unchanged:
        routing must price the miss path, not the hit path)."""
        return self.inner.cost

    @property
    def requires_query_vecs(self) -> bool:
        """Whether the inner backend consumes embedded query vectors."""
        return self.inner.requires_query_vecs

    @property
    def nested_topk(self) -> bool:
        """The inner backend's: a cached row is the inner backend's row."""
        return is_nested(self.inner)

    @property
    def size(self) -> int:
        """Corpus passages indexed by the inner backend."""
        return self.inner.size

    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Fetch passage payloads from the inner backend."""
        return self.inner.get_passages(ids)

    # -- cache core ----------------------------------------------------------
    def _keys(
        self, queries: Sequence[str], query_vecs: jnp.ndarray | None, k: int
    ) -> list[tuple]:
        """Per-row cache keys covering *every* input the inner backend reads:
        the exact vector bytes AND the query string for vector backends
        (hybrid consumes both — its BM25 half scores the text, so a
        vector-only key could alias two queries whose embeddings collide),
        the query string alone for lexical ones, plus ``k``."""
        if self.requires_query_vecs:
            if query_vecs is None:
                raise ValueError(f"backend {self.name!r} requires query_vecs")
            vecs = np.asarray(query_vecs, np.float32)
            return [(k, vecs[i].tobytes(), queries[i]) for i in range(vecs.shape[0])]
        return [(k, q) for q in queries]

    def search_batch_stats(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray, CacheStats]:
        """:meth:`search_batch` plus this call's hit/miss/eviction delta.

        The serving ``retrieve`` stage calls this variant so cache activity
        is attributed to the exact micro-batch that incurred it (snapshotting
        cumulative counters around the call would misattribute under
        concurrent stages).
        """
        # queries may be None for backends that ignore text (dense/IVF do;
        # the serving retrieve stage always supplies it). The original value
        # is forwarded to the inner backend untouched, so a text-reading
        # backend (hybrid's BM25 half) fails as loudly wrapped as unwrapped
        # instead of silently scoring substituted empty strings.
        if self.requires_query_vecs:
            if query_vecs is None:
                raise ValueError(f"backend {self.name!r} requires query_vecs")
            n = int(np.asarray(query_vecs).shape[0])
        else:
            n = len(queries) if queries is not None else 0
        key_texts = list(queries) if queries is not None else [""] * n
        if n == 0:
            out = self.inner.search_batch(queries, query_vecs, k)
            return np.asarray(out[0], np.float32), np.asarray(out[1], np.int32), CacheStats()
        keys = self._keys(key_texts, query_vecs, k)

        rows: list[tuple[np.ndarray, np.ndarray] | None] = [None] * n
        miss_pos: list[int] = []
        hits = 0
        with self._lock:
            for i, key in enumerate(keys):
                cached = self._lru.get(key)
                if cached is not None:
                    self._lru.move_to_end(key)
                    rows[i] = cached
                    hits += 1
                else:
                    miss_pos.append(i)

        evictions = 0
        if miss_pos:
            sub_queries = (
                [queries[i] for i in miss_pos] if queries is not None else None
            )
            sub_vecs = (
                jnp.asarray(np.asarray(query_vecs, np.float32)[miss_pos])
                if self.requires_query_vecs
                else None
            )
            scores, ids = self.inner.search_batch(sub_queries, sub_vecs, k)
            scores_np = np.asarray(scores, np.float32)
            ids_np = np.asarray(ids, np.int32)
            with self._lock:
                for r, i in enumerate(miss_pos):
                    # copy: a row *view* would pin the whole miss-batch
                    # matrices in memory for as long as it stays cached
                    row = (scores_np[r].copy(), ids_np[r].copy())
                    rows[i] = row
                    # duplicate keys inside one batch each count as a miss
                    # (each row paid the inner search) but insert once
                    self._lru[keys[i]] = row
                    self._lru.move_to_end(keys[i])
                while len(self._lru) > self.capacity:
                    self._lru.popitem(last=False)
                    evictions += 1

        delta = CacheStats(hits=hits, misses=len(miss_pos), evictions=evictions)
        with self._lock:
            self._stats = self._stats + delta
        out_scores = np.stack([r[0] for r in rows])  # type: ignore[index]
        out_ids = np.stack([r[1] for r in rows])  # type: ignore[index]
        return out_scores, out_ids, delta

    def search_batch(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched search with per-row caching — result rows are bit-identical
        to the inner backend's, whether served from cache or computed."""
        scores, ids, _ = self.search_batch_stats(queries, query_vecs, k)
        return scores, ids

    # -- observability --------------------------------------------------------
    def stats(self) -> CacheStats:
        """Cumulative hit/miss/eviction totals since construction."""
        with self._lock:
            return self._stats

    def clear(self) -> None:
        """Drop every cached row (counters are preserved)."""
        with self._lock:
            self._lru.clear()

    def __len__(self) -> int:
        """Number of rows currently cached."""
        with self._lock:
            return len(self._lru)

    def __bool__(self) -> bool:
        """Always truthy: ``__len__`` alone would make an *empty* cache
        falsy, silently failing ``if backend`` checks on the wrapped
        object (a backend exists regardless of its cache fill)."""
        return True


def wrap_cached(
    backends: Mapping[str, RetrievalBackend], *, capacity: int
) -> dict[str, RetrievalBackend]:
    """Wrap every backend of an engine's backend map in a
    :class:`CachedBackend` of the given capacity. Already-cached backends
    are left as-is.

    .. deprecated:: Prefer :func:`repro.retrieval.build_backend_stack` with
       ``BackendStackConfig(cache_size=...)`` — the one construction path
       that also gets the shard/fault/resilience ordering right. This shim
       stays for direct single-layer wrapping.
    """
    return {
        name: b if isinstance(b, CachedBackend) else CachedBackend(b, capacity=capacity)
        for name, b in backends.items()
    }


def scale_backends(
    backends: Mapping[str, RetrievalBackend],
    index=None,
    *,
    cache_size: int = 0,
    shards: int = 1,
) -> dict[str, RetrievalBackend]:
    """Shard the dense backend, then cache everything — now a thin shim.

    .. deprecated:: Prefer :func:`repro.retrieval.build_backend_stack`,
       which this delegates to (so ordering can never drift between the two
       paths) and which also covers fault injection, resilience, and the
       device-sharding knobs this signature predates.
    """
    from repro.retrieval.stack import BackendStackConfig, build_backend_stack

    return build_backend_stack(
        backends,
        BackendStackConfig(shards=shards, cache_size=cache_size),
        index=index,
    )


def cache_stats_view(backends: Mapping[str, RetrievalBackend]) -> dict[str, dict[str, int]]:
    """Cumulative per-backend cache counters for every cache-wrapped entry
    of a backend map — what the CLI and examples print after a run. Walks
    the decorator chain (``.inner``), so a cache nested under an outer
    wrapper (e.g. ResilientBackend) still reports."""
    out: dict[str, dict[str, int]] = {}
    for name, b in backends.items():
        for _ in range(16):  # bounded: decorator chains are shallow
            if isinstance(b, CachedBackend):
                out[name] = b.stats().as_dict()
                break
            b = getattr(b, "inner", None)
            if b is None:
                break
    return out
