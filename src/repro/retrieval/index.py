"""Dense MIPS retrieval index — the framework's FAISS role (paper §V.E).

``DenseIndex`` holds L2-normalized passage embeddings so inner product ==
cosine similarity ("FAISS inner-product index", §V.E). Three search paths:

* :meth:`search` / :meth:`search_batch` — single-device exact MIPS through a
  *cached jit-compiled closure* per ``(k, scorer)``: queries are chunked into
  fixed ``(Q_BLOCK, d)`` blocks (zero-padded), so every search — one query or
  a thousand — runs the same compiled program and nothing retraces per query.
  ``scorer`` selects the implementation:

  - ``"blocked"`` (default): block matmul (``topk.mips_scores``) + exact
    two-stage top-k by 128-column group maxima (``topk.blocked_topk``),
    which never sorts the score row — the served scorer.
  - ``"pallas"``: the fused Pallas ``mips_topk`` TPU kernel
    (``kernels.mips_topk``). Pass ``interpret=True`` to run it off-TPU.

  Both take the corpus as a program argument (never a compiled-in
  constant), zero-padded to whole ``SCORE_BLOCK`` row blocks with the pad
  rows masked. The fixed query block makes batched retrieval
  *bit-identical* to per-query retrieval (a row's scores depend only on its
  own block row), and the fixed corpus block makes it bit-identical across
  shards (a score never depends on how many rows share the matmul).
* :meth:`sharded_search_fn` — corpus rows sharded over mesh axes with
  ``shard_map``; per-shard local top-k then hierarchical merge
  (``topk.distributed_topk``). This is the production path and the
  ``retrieval_cand`` dry-run cell.
* IVF approximate search lives in ``ivf.py`` and reuses this index's vectors.

Retrieval confidence = max similarity among returned hits (paper §VI.B),
logged per query and consumed by the low-confidence guardrail.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import SCORE_BLOCK
from repro.retrieval.chunking import Passage
from repro.retrieval.embedder import Embedder
from repro.retrieval.topk import blocked_topk, distributed_topk, mips_scores

# Fixed query-block width for the compiled search closures. Every search is
# padded to a multiple of this, so the compiled matmul shape — and therefore
# each row's floating-point result — is independent of the caller's batch
# size. 8 matches the Pallas kernel's default block_q.
Q_BLOCK = 8

SCORERS = ("blocked", "pallas")


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Hits for one query, descending by score."""

    passage_ids: np.ndarray  # (k,) int32
    scores: np.ndarray  # (k,) float32

    @property
    def confidence(self) -> float:
        """Max cosine similarity — the paper's retrieval confidence."""
        return float(self.scores[0]) if self.scores.size else float("nan")


def l2_normalize(x: jnp.ndarray, eps: float = 1e-9) -> jnp.ndarray:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), eps)


def _block_width(k: int) -> int:
    """Rows a corpus pads to a multiple of: :data:`~repro.kernels.SCORE_BLOCK`,
    doubled until it holds ``k`` (the Pallas kernel selects its top-k
    within one block). Shared by both scorers, the single-device path and
    the per-shard sharded path, so every corpus pads identically and both
    scorers share one device copy."""
    bn = SCORE_BLOCK
    while bn < k:
        bn *= 2
    return bn


def search_program(
    k: int, n_valid: int, scorer: str = "blocked", *, interpret: bool = False
) -> Callable:
    """The jit-compiled single-device search: ``(corpus, (Q_BLOCK, d)) →
    (scores (Q_BLOCK, k), ids (Q_BLOCK, k))`` over a corpus zero-padded to
    whole score blocks, whose rows past ``n_valid`` are never candidates.
    ``scorer`` is ``"blocked"`` (block matmul + two-stage top-k) or
    ``"pallas"`` (the fused ``mips_topk`` kernel)."""
    if scorer == "blocked":

        def core(corpus: jax.Array, q: jax.Array):
            with jax.named_scope("score"):
                scores = mips_scores(l2_normalize(q), corpus)  # (bq, n_padded)
                if corpus.shape[0] != n_valid:  # pad rows are never candidates
                    col = jnp.arange(corpus.shape[0])[None, :]
                    scores = jnp.where(col < n_valid, scores, -jnp.inf)
            with jax.named_scope("select"):
                return blocked_topk(scores, k)

    elif scorer == "pallas":
        from repro.kernels.mips_topk.kernel import mips_topk_pallas

        bn = _block_width(k)

        def core(corpus: jax.Array, q: jax.Array):
            return mips_topk_pallas(
                l2_normalize(q), corpus, k,
                block_q=Q_BLOCK, block_n=bn, n_valid=n_valid, interpret=interpret,
            )

    else:
        raise ValueError(f"unknown scorer {scorer!r}; expected one of {SCORERS}")
    return jax.jit(core)


class DenseIndex:
    """Exact MIPS index over passage embeddings."""

    def __init__(
        self,
        embeddings: jnp.ndarray,
        passages: Sequence[Passage] | None = None,
        *,
        assume_normalized: bool = False,
    ):
        if embeddings.ndim != 2:
            raise ValueError(f"embeddings must be (n, d), got {embeddings.shape}")
        # assume_normalized: the rows are already unit-norm (e.g. a slice of
        # another index's .embeddings — the ShardedBackend construction path).
        # Skipping the re-normalization matters for bit-exactness: dividing a
        # unit vector by its ~1.0 norm perturbs last-bit floats.
        #
        # Normalized host (numpy) rows stay on the host: a single-device
        # search places them on the default device when it first needs them
        # (_device_corpus), and the device-sharded backend places each shard
        # straight onto its own device, so a corpus larger than one chip is
        # never staged whole on the first.
        if assume_normalized and isinstance(embeddings, np.ndarray):
            self.embeddings = np.asarray(embeddings, np.float32)
        else:
            emb = jnp.asarray(embeddings, jnp.float32)
            self.embeddings = emb if assume_normalized else l2_normalize(emb)
        self.passages = list(passages) if passages is not None else None
        if self.passages is not None and len(self.passages) != embeddings.shape[0]:
            raise ValueError("passages/embeddings length mismatch")
        # (k, scorer, interpret) → (jit-compiled fixed-shape search program,
        # the device corpus it takes as its first argument)
        self._fn_cache: dict[tuple, tuple[Callable, jax.Array]] = {}
        # block width → device corpus zero-padded to a multiple of it
        self._padded_corpus: dict[int, jax.Array] = {}
        # The device-sharded search's state (device_sharded_search) lives
        # here too, so every backend and engine over this index shares it:
        # (mesh, shard axes, rows per shard) → the corpus placed across the
        # mesh, and that key plus (k, scorer, interpret, n_valid) → the
        # compiled shard_map program.
        self._sharded_corpus: dict[tuple, jax.Array] = {}
        self._sharded_fn_cache: dict[tuple, Callable] = {}

    # -- construction ---------------------------------------------------------
    @classmethod
    def build(cls, passages: Sequence[Passage], embedder: Embedder) -> tuple["DenseIndex", int]:
        """Embed passages once and build the index (paper: "The corpus is
        embedded once; all queries share the same FAISS index").

        Returns (index, index_embedding_tokens) — the offline billing
        bookkeeping of §V.D.
        """
        texts = [p.text for p in passages]
        emb = embedder.embed(texts)
        return cls(emb, passages), embedder.billed_tokens(texts)

    @property
    def size(self) -> int:
        return self.embeddings.shape[0]

    @property
    def dim(self) -> int:
        return self.embeddings.shape[1]

    # -- single-device search ---------------------------------------------------
    def _device_corpus(self, bn: int) -> jax.Array:
        """The corpus zero-padded to a multiple of ``bn`` rows, on the
        default device, placed on first use. Host rows are padded on the
        host, so the device only ever holds the padded copy."""
        corpus = self._padded_corpus.get(bn)
        if corpus is None:
            pad = (-self.size) % bn
            emb = self.embeddings
            if pad and isinstance(emb, np.ndarray):
                emb = np.concatenate([emb, np.zeros((pad, self.dim), np.float32)])
            elif pad:
                emb = jnp.concatenate([emb, jnp.zeros((pad, self.dim), jnp.float32)])
            corpus = self._padded_corpus[bn] = jnp.asarray(emb)
        return corpus

    def _search_fn(self, k: int, scorer: str, interpret: bool) -> tuple[Callable, jax.Array]:
        """Cached jit-compiled ``(corpus, (Q_BLOCK, d)) → ((Q_BLOCK, k),
        (Q_BLOCK, k))`` search program and the device corpus it takes —
        compiled once per (k, scorer), reused by every subsequent
        query/batch so the serving hot path never retraces. The corpus is an
        argument, never a captured constant: a constant would be compiled
        into the program (past the 2 GB serialized-program limit at 10⁶×768
        f32) and held on the device a second time."""
        key = (k, scorer, interpret)
        entry = self._fn_cache.get(key)
        if entry is None:
            fn = search_program(k, self.size, scorer, interpret=interpret)
            entry = self._fn_cache[key] = (fn, self._device_corpus(_block_width(k)))
        return entry

    def search_batch(
        self,
        query_vecs: jnp.ndarray,
        k: int,
        *,
        scorer: str = "blocked",
        interpret: bool = False,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(nq, d) → (scores (nq, k), ids (nq, k)), descending per row.

        Queries run through the cached compiled closure in fixed ``Q_BLOCK``
        chunks (zero-padded); arbitrary nq — including non-multiples of the
        kernel blocks — is handled by the auto-padding. jit-compatible: all
        padding/chunking is shape-static jnp.
        """
        k = min(k, self.size)
        if query_vecs.ndim != 2:
            raise ValueError(f"query_vecs must be (nq, d), got {query_vecs.shape}")
        nq = query_vecs.shape[0]
        if nq == 0:
            return jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32)
        fn, corpus = self._search_fn(k, scorer, interpret)
        pad = (-nq) % Q_BLOCK
        if isinstance(query_vecs, jax.core.Tracer):
            # traced (inside a caller's jit): stay pure-jnp
            q = jnp.asarray(query_vecs, jnp.float32)
            if pad:
                q = jnp.concatenate([q, jnp.zeros((pad, q.shape[1]), jnp.float32)], axis=0)
            outs = [fn(corpus, q[s : s + Q_BLOCK]) for s in range(0, q.shape[0], Q_BLOCK)]
            vals = jnp.concatenate([v for v, _ in outs], axis=0)[:nq]
            ids = jnp.concatenate([i for _, i in outs], axis=0)[:nq]
            return vals, ids
        # concrete inputs: pad/chunk/reassemble on host so the only XLA work
        # is the fixed-shape closure — batch sizes never trigger op compiles
        with TraceAnnotation("repro.search", k=k, nq=nq):
            q = np.asarray(query_vecs, np.float32)
            if pad:
                q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)], axis=0)
            vals_np, ids_np = [], []
            for chunk, s in enumerate(range(0, q.shape[0], Q_BLOCK)):
                with TraceAnnotation("repro.search.dispatch", chunk=chunk):
                    v, i = fn(corpus, jnp.asarray(q[s : s + Q_BLOCK]))
                with TraceAnnotation("repro.search.fetch", chunk=chunk):
                    vals_np.append(np.asarray(v, np.float32))
                    ids_np.append(np.asarray(i, np.int32))
            vals = np.concatenate(vals_np, axis=0)[:nq] if len(vals_np) > 1 else vals_np[0][:nq]
            ids = np.concatenate(ids_np, axis=0)[:nq] if len(ids_np) > 1 else ids_np[0][:nq]
            return jnp.asarray(vals), jnp.asarray(ids)

    def search(
        self,
        query_vec: jnp.ndarray,
        k: int,
        *,
        scorer: str = "blocked",
        interpret: bool = False,
    ) -> SearchResult:
        """Single-query wrapper over :meth:`search_batch` — same compiled
        closure, same ``scorer`` options, bit-identical scores."""
        scores, ids = self.search_batch(
            jnp.asarray(query_vec)[None, :], k, scorer=scorer, interpret=interpret
        )
        return SearchResult(np.asarray(ids[0], np.int32), np.asarray(scores[0], np.float32))

    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        if self.passages is None:
            raise ValueError("index built without passage payloads")
        return [self.passages[int(i)] for i in ids]

    # -- distributed search -------------------------------------------------------
    def device_sharded_search(
        self,
        mesh: jax.sharding.Mesh,
        k: int,
        shard_axes: tuple[str, ...],
        *,
        scorer: str = "blocked",
        interpret: bool = False,
    ) -> tuple[Callable, jax.Array]:
        """The :meth:`sharded_search_fn` program for ``k`` and the corpus it
        takes, placed across ``mesh``: built on first use and kept on this
        index, so every backend over it (one per engine) shares one placed
        copy per device and one compiled program per ``k``.

        Each shard holds its share of the rows, padded to whole score blocks
        (``_block_width(k)``) so it scores exactly as the unsharded index;
        rows past the corpus are zeros that the program masks
        (``n_valid``). Each shard is placed straight from the host onto its
        own device, so no device ever holds more than its shard.
        """
        n_shards = int(np.prod([mesh.shape[a] for a in shard_axes]))
        bn = _block_width(k)
        rows_per = math.ceil(math.ceil(self.size / n_shards) / bn) * bn
        n_valid = self.size if rows_per * n_shards != self.size else None
        placement = (mesh, tuple(shard_axes), rows_per)
        key = placement + (k, scorer, interpret, n_valid)
        fn = self._sharded_fn_cache.get(key)
        if fn is None:
            fn, _ = self.sharded_search_fn(
                mesh, k, shard_axes, scorer=scorer, interpret=interpret, n_valid=n_valid
            )
            self._sharded_fn_cache[key] = fn
        corpus = self._sharded_corpus.get(placement)
        if corpus is None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            host = np.asarray(self.embeddings, np.float32)
            n, d = host.shape
            total = rows_per * n_shards

            def shard_rows(index: tuple[slice, ...]) -> np.ndarray:
                start, stop, _ = index[0].indices(total)
                rows = host[min(start, n) : min(stop, n)]
                if rows.shape[0] < stop - start:  # the padded tail shard(s)
                    fill = np.zeros((stop - start - rows.shape[0], d), np.float32)
                    rows = np.concatenate([rows, fill])
                return rows

            corpus = self._sharded_corpus[placement] = jax.make_array_from_callback(
                (total, d), NamedSharding(mesh, P(tuple(shard_axes), None)), shard_rows
            )
        return fn, corpus

    def sharded_search_fn(
        self,
        mesh: jax.sharding.Mesh,
        k: int,
        shard_axes: tuple[str, ...],
        *,
        scorer: str = "blocked",
        interpret: bool = False,
        n_valid: int | None = None,
    ):
        """Build a shard_map'd exact search over corpus rows.

        Corpus rows are sharded over ``shard_axes`` (e.g. ``("data","model")``
        → 256-way row sharding); queries are replicated; each shard scores
        its rows (``scorer="blocked"`` matmul + two-stage top-k, or
        ``"pallas"`` for the fused ``mips_topk`` kernel per shard), computes
        a local top-k, and the k-candidate lists merge with one all-gather
        per axis — the whole search is a single device program with no host
        round-trip between shards. Returns ``fn(corpus, queries) ->
        (scores, ids)`` with global ids, plus the shard count.

        Non-divisible corpora: pass a corpus zero-padded so rows divide the
        shard count and set ``n_valid`` to the real row count — each shard
        masks its own residue columns (a *traced* quantity: it depends on
        ``lax.axis_index``) before the local top-k, so padded rows can never
        enter the candidate set. Requires ``k <= n_valid`` (callers clamp,
        exactly as :meth:`search_batch` clamps k to the corpus size). For
        ``scorer="pallas"``, per-shard rows must additionally be a multiple
        of the score block width (``_block_width(k)``, as the single-device
        path pads).
        """
        from jax.sharding import PartitionSpec as P

        if scorer not in SCORERS:
            raise ValueError(f"unknown scorer {scorer!r}; expected one of {SCORERS}")
        n_shards = int(np.prod([mesh.shape[a] for a in shard_axes]))
        corpus_spec = P(shard_axes, None)
        out_spec = P(None, None)
        if scorer == "pallas":
            from repro.kernels.mips_topk.kernel import mips_topk_pallas

        def local_search(corpus_shard: jnp.ndarray, queries: jnp.ndarray):
            # global row offset of this shard
            idx = jax.lax.axis_index(shard_axes)
            rows = corpus_shard.shape[0]
            start = idx * rows
            with jax.named_scope("score"):
                queries = l2_normalize(queries)  # cosine, matching search_batch
            kk = min(k, rows)
            if scorer == "pallas":
                bn = _block_width(kk)
                mask = None
                if n_valid is not None:
                    # traced per-shard residue mask: real global row < n_valid
                    mask = ((start + jnp.arange(rows)) < n_valid).astype(jnp.float32)
                v, i = mips_topk_pallas(
                    queries, corpus_shard, kk,
                    block_q=queries.shape[0], block_n=bn,
                    valid_mask=mask, interpret=interpret,
                )
            else:
                with jax.named_scope("score"):
                    scores = mips_scores(queries, corpus_shard)  # (nq, rows_local)
                    if n_valid is not None:
                        col = start + jnp.arange(rows)[None, :]
                        scores = jnp.where(col < n_valid, scores, -jnp.inf)
                with jax.named_scope("select"):
                    v, i = blocked_topk(scores, kk)
            with jax.named_scope("merge"):
                i = i + start  # globalize
                for ax in shard_axes:
                    v, i = distributed_topk(v, i, k, ax)
            return v, i

        return jax.jit(
            jax.shard_map(
                local_search,
                mesh=mesh,
                in_specs=(corpus_spec, P(None, None)),
                out_specs=(out_spec, out_spec),
                check_vma=False,
            )
        ), n_shards
