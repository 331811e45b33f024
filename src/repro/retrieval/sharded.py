"""Sharded retrieval: corpora larger than one host's index, one config flag.

The scaling seam the ROADMAP's heavy-traffic north star needs: RAGO
(Jiang et al., 2025) shows retrieval sharding is — with caching — the
dominant systems lever for RAG serving, and "Towards Understanding Systems
Trade-offs in RAG" (2024) shows retrieval cost dominates exactly the
heavy-bundle regime the router prices. :class:`ShardedBackend` partitions
the corpus into S contiguous row ranges and runs the per-shard searches
under one of three executions (plus ``"auto"``), selected by
``from_dense(..., execution=...)``:

* ``"threads"`` — per-shard inner backends fanned out on the host
  (optionally on a thread pool), ids globalized, per-shard top-k candidate
  lists merged with the repo's fused top-k primitive
  (:func:`repro.retrieval.topk.merge_topk`). Runs anywhere, but every
  query pays S Python dispatches plus S-1 host-side merges — and the
  *pooled* variant pays them under one GIL, which measurably loses to
  running the shards inline for jit-bound work (the serving bench's S=4
  collapse). ``"auto"`` therefore resolves to inline threads or process
  workers, never a thread pool (:func:`resolve_execution`).
* ``"process"`` — the same host fan-out on persistent spawned worker
  processes, one per shard (:class:`ProcessShardedBackend`): each worker
  owns its corpus slice and jit closures, searches run GIL-free across
  cores, and the parent merges with the identical fused top-k.
* ``"device"`` — the whole search lowers onto a jax device mesh as a
  single ``shard_map``'d program (:class:`DeviceShardedBackend`): corpus
  rows are row-partitioned across the mesh per
  :meth:`~repro.distributed.partition.ShardingPolicy.corpus_rows`, queries
  replicate per :func:`mesh_layout`, each shard scores its rows in place
  (blocked matmul or the fused pallas ``mips_topk`` kernel), and the
  per-shard→global top-k merge happens **on device** via
  :func:`~repro.retrieval.topk.distributed_topk` — one all-gather of S·k
  candidates, no host round-trip. This is the production path; the threads
  path remains the portable fallback and differential-testing oracle.

Exactness — the property every test here pins, identical for both
executions:

* Merging per-shard top-k lists of length k loses nothing for a global
  top-k (any global top-k element is a local top-k element of its shard —
  the same argument ``topk.distributed_topk`` rests on).
* Per-shard dense scoring is **bit-identical** to unsharded scoring: a
  ``(Q_BLOCK, d) @ (d, n_shard)`` matmul reduces over ``d`` exactly like
  the full-corpus matmul (the reduction axis is unchanged; only output
  columns are partitioned). The threads path slices the *already-
  normalized* embeddings (``DenseIndex(assume_normalized=True)``); the
  device path partitions the same normalized rows across the mesh — no
  value is ever re-normalized either way.
* Tie-breaking matches too: within a shard ``top_k`` prefers the lowest
  local id, and both merges — the host's left-to-right ``merge_topk`` and
  the device's shard-major all-gather — prefer the lowest shard, so equal
  scores resolve to the lowest *global* id, exactly like the unsharded
  path.
* Non-divisible corpora: the threads path gives the first ``n % S`` shards
  one extra row (``shard_bounds``); the device path zero-pads rows up to a
  shard multiple and each shard masks its own residue columns before the
  local top-k (a *traced* mask — the residue depends on
  ``lax.axis_index``), so pad rows can never enter the candidate set.

Together these make a sharded dense backend a drop-in for ``"dense"``:
drained serving runs are bit-identical to the unsharded engine at every
pipeline setting (tests/test_cache_sharded.py and
tests/test_sharded_device.py sweep this).
"""

from __future__ import annotations

import bisect
import dataclasses
import multiprocessing
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.retrieval.backend import (
    BackendCost,
    BM25Backend,
    DenseBackend,
    IVFBackend,
    RetrievalBackend,
    is_nested,
)
from repro.retrieval.chunking import Passage
from repro.retrieval.index import Q_BLOCK, DenseIndex
from repro.retrieval.topk import merge_topk
from repro.runtime import accelerator_attached, refuse_children_on_accelerator

# "auto" resolves at construction time (resolve_execution): inline host
# fan-out on single-core hosts or an accelerator, process workers when real
# CPU cores do the searching.
EXECUTIONS = ("threads", "process", "device", "auto")


def resolve_execution(execution: str, *, n_shards: int, workers: int = 0) -> str:
    """Resolve ``"auto"`` to a concrete dense-shard execution.

    The threaded fan-out is a pessimization for jit-bound shards — S GIL-
    serialized dispatches plus pool handoffs per search (the 1158→55 qps
    S=4 collapse the serving bench exposed) — so auto never picks a thread
    pool: single shard or single core → ``"threads"`` with the serial
    inline fan-out (no pool, no handoff); multi-core and S > 1 →
    ``"process"`` (one spawned worker per shard, GIL-free) — but only when
    the default backend is the CPU: on an accelerator this process holds
    the chip and the workers could not reach it, so auto stays inline. An
    explicit ``workers`` request is honored as the thread pool the caller
    asked for.
    """
    if execution != "auto":
        return execution
    if workers:
        return "threads"
    if n_shards > 1 and (os.cpu_count() or 1) > 1 and not accelerator_attached():
        return "process"
    return "threads"


def merge_shard_parts(
    parts: "Sequence[tuple[np.ndarray, np.ndarray]]", k: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Merge per-shard (scores, globalized ids) candidates into the global
    top-k; shared by every host-side fan-out (threads and process).

    Left-to-right :func:`~repro.retrieval.topk.merge_topk` — pure selection
    over already-computed scores, so no arithmetic (and no float drift)
    happens at merge time; lowest shard wins ties, reconstructing the
    unsharded lowest-global-id order. IVF shards keep their ``-inf``
    degenerate-probe padding through the merge (per-shard truncation would
    discard candidates another shard can't supply); the result narrows
    once, globally, to the widest all-finite prefix — exactly what the
    unsharded IVFBackend does. Dense and BM25 rows are always finite, so
    that truncation is a no-op for them.

    Returns ``(scores, ids, n_merges)`` with the merge count for the
    :class:`ShardCounters` the CI scaling cell pins.
    """
    vals = jnp.asarray(parts[0][0])
    ids = jnp.asarray(parts[0][1])
    n_merges = 0
    for sv, si in parts[1:]:
        width = min(k, vals.shape[-1] + sv.shape[-1])
        vals, ids = merge_topk(vals, ids, jnp.asarray(sv), jnp.asarray(si), width)
        n_merges += 1
    vals_np = np.asarray(vals, np.float32)
    ids_np = np.asarray(ids, np.int32)
    bad = ~np.isfinite(vals_np)
    if bad.any():
        w = int((~bad).sum(axis=1).min())
        vals_np, ids_np = vals_np[:, :w], ids_np[:, :w]
    return vals_np, ids_np, n_merges


def shard_bounds(n: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous near-equal ``[start, stop)`` row ranges for ``n`` rows.

    ``numpy.array_split`` semantics: the first ``n % n_shards`` shards get
    one extra row, so non-divisible corpus sizes are first-class (and
    pinned by the property tests).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n_shards > n:
        raise ValueError(f"n_shards={n_shards} > corpus rows n={n}")
    base, extra = divmod(n, n_shards)
    bounds, start = [], 0
    for s in range(n_shards):
        stop = start + base + (1 if s < extra else 0)
        bounds.append((start, stop))
        start = stop
    return bounds


def mesh_layout(policy=None):
    """``shard_map`` spec triple ``(corpus, queries, out)`` for this
    partitioning on a device mesh.

    Corpus rows shard over the data axes, queries and merged outputs
    replicate — the layout ``DenseIndex.sharded_search_fn`` executes and
    ``execution="device"`` places its corpus with. Takes a
    :class:`~repro.distributed.partition.ShardingPolicy` (default
    constructed) so multi-pod meshes reuse their axis-name bundle.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.partition import ShardingPolicy

    policy = policy or ShardingPolicy()
    return policy.corpus_rows(), P(None, None), P(None, None)


@dataclasses.dataclass
class ShardCounters:
    """Deterministic work counters for a sharded backend — what the CI
    gate's scaling-sweep cell pins (qps is telemetry; these are exact).

    ``searches`` counts ``search_batch`` calls; ``shard_searches`` counts
    per-shard local search executions (threads: S per call; device: S per
    dispatched query chunk — the device path redispatches its fixed-shape
    program per ``q_block``-wide chunk, the same discipline as
    ``DenseIndex``);
    ``merges`` counts top-k merge operations (threads: S-1 pairwise
    ``merge_topk`` per call; device: one collective merge per chunk per
    mesh axis).
    """

    searches: int = 0
    shard_searches: int = 0
    merges: int = 0

    def as_dict(self) -> dict[str, int]:
        return {
            "searches": self.searches,
            "shard_searches": self.shard_searches,
            "merges": self.merges,
        }


class ShardedBackend:
    """S-way partitioned retrieval behind the one-backend protocol.

    This class *is* the ``execution="threads"`` path: ``shards`` are inner
    backends over contiguous corpus partitions and ``offsets`` their global
    row offsets. ``workers > 1`` fans the per-shard searches out on a
    thread pool (results are combined in shard order, so threading never
    changes the answer). Use :meth:`from_dense` with
    ``execution="device"`` for the ``shard_map``-lowered variant
    (:class:`DeviceShardedBackend`).
    """

    execution = "threads"

    def __init__(
        self,
        shards: Sequence[RetrievalBackend],
        offsets: Sequence[int],
        *,
        name: str | None = None,
        cost: BackendCost | None = None,
        workers: int = 0,
    ):
        if not shards:
            raise ValueError("need at least one shard")
        if len(shards) != len(offsets):
            raise ValueError(f"{len(shards)} shards but {len(offsets)} offsets")
        self.shards = list(shards)
        self.offsets = [int(o) for o in offsets]
        if self.offsets != sorted(self.offsets):
            raise ValueError("offsets must be ascending (contiguous partitions)")
        self.name = name if name is not None else self.shards[0].name
        self.cost = cost if cost is not None else self.shards[0].cost
        self.requires_query_vecs = any(s.requires_query_vecs for s in self.shards)
        # the lowest-id-first merge keeps nested shards' rows nested
        self.nested_topk = all(is_nested(s) for s in self.shards)
        self.workers = max(0, int(workers))
        self._pool = ThreadPoolExecutor(max_workers=self.workers) if self.workers > 1 else None
        self.counters = ShardCounters()

    @classmethod
    def from_dense(
        cls,
        index: DenseIndex,
        *,
        n_shards: int,
        workers: int = 0,
        scorer: str = "blocked",
        interpret: bool = False,
        execution: str = "threads",
        mesh: jax.sharding.Mesh | None = None,
        q_block: int | None = None,
    ) -> "ShardedBackend":
        """Partition a built :class:`DenseIndex` into an S-way sharded dense
        backend — the ``--shards`` CLI path.

        ``execution="threads"`` slices the index's *normalized* embeddings
        (and passage payloads) into contiguous per-shard
        ``DenseIndex(..., assume_normalized=True)`` backends searched from
        the host. ``execution="process"`` returns a
        :class:`ProcessShardedBackend`: one persistent spawned worker per
        shard, each owning its slice's index and jit closures, searched
        GIL-free over pipes and merged with the same fused top-k.
        ``execution="device"`` returns a :class:`DeviceShardedBackend`
        that row-partitions the same embeddings across a device mesh
        (``mesh`` defaults to a 1-axis ``"data"`` mesh over the first
        ``n_shards`` visible devices) and runs search + merge as one
        ``shard_map``'d program. ``execution="auto"`` picks between inline
        threads and process by host core count (:func:`resolve_execution`
        — the threaded pool is never auto-selected: fanning jit-bound
        shards across GIL-sharing threads is the measured S=4 collapse).
        All are bit-identical to the unsharded index.
        """
        if execution not in EXECUTIONS:
            raise ValueError(f"unknown execution {execution!r}; expected one of {EXECUTIONS}")
        execution = resolve_execution(execution, n_shards=n_shards, workers=workers)
        if execution == "device":
            if workers:
                raise ValueError("workers is a threads-execution knob; device execution ignores the host pool")
            return DeviceShardedBackend(
                index, n_shards=n_shards, mesh=mesh, scorer=scorer,
                interpret=interpret, q_block=q_block,
            )
        if execution == "process":
            if workers:
                raise ValueError(
                    "workers is a threads-execution knob; process execution "
                    "owns one worker process per shard"
                )
            if q_block is not None:
                raise ValueError(
                    "q_block is a device-execution knob; the process path has "
                    "no fixed-shape chunking to tune"
                )
            return ProcessShardedBackend(
                index, n_shards=n_shards, scorer=scorer, interpret=interpret
            )
        if q_block is not None:
            raise ValueError(
                "q_block is a device-execution knob; the threads path has no "
                "fixed-shape chunking to tune"
            )
        bounds = shard_bounds(index.size, n_shards)
        shards: list[RetrievalBackend] = []
        for start, stop in bounds:
            sub_passages = index.passages[start:stop] if index.passages is not None else None
            sub = DenseIndex(
                index.embeddings[start:stop], sub_passages, assume_normalized=True
            )
            shards.append(DenseBackend(sub, scorer=scorer, interpret=interpret))
        return cls(shards, [b[0] for b in bounds], workers=workers)

    @classmethod
    def from_bm25(
        cls,
        backend: BM25Backend,
        *,
        n_shards: int,
        workers: int = 0,
    ) -> "ShardedBackend":
        """Partition a built :class:`BM25Backend` into S contiguous-range
        lexical shards — sparse sharding's ``bm25`` entry point.

        Each shard wraps a :meth:`BM25Index.shard` view, which replicates
        the corpus-*global* per-posting idf/avgdl statistics (in fact the
        exact precomputed contribution floats), so per-(query, passage)
        scores — and therefore the merged top-k — are bit-identical to the
        unsharded backend. Sentinel slots (score 0.0) sort after every real
        lexical hit (strictly positive) in the merge, so the sentinel-suffix
        contract survives sharding. Threads execution only: postings are a
        host-built ragged structure with no mesh placement (dense
        ``execution="device"`` is a dense-matmul-shaped program).
        """
        bounds = shard_bounds(backend.bm25.n_passages, n_shards)
        views = backend.bm25.shard(n_shards)
        shards = [
            BM25Backend(v, backend.passages[start:stop])
            for v, (start, stop) in zip(views, bounds)
        ]
        return cls(shards, [b[0] for b in bounds], workers=workers)

    @classmethod
    def from_ivf(
        cls,
        backend: IVFBackend,
        *,
        n_shards: int,
        workers: int = 0,
    ) -> "ShardedBackend":
        """Partition a built :class:`IVFBackend` into S contiguous-range
        probed shards — sparse sharding's ``ivf`` entry point.

        Each shard wraps an :meth:`IVFIndex.shard` view, which replicates
        the *global* k-means centroids (every shard probes exactly the
        clusters the unsharded index probes) and keeps only its row range's
        inverted-list members. The per-shard candidate set is the unsharded
        candidate set intersected with the shard, so the lowest-shard-wins
        merge reconstructs the unsharded canonical row order exactly.
        Per-shard adapters are built with ``truncate_nonfinite=False``:
        degenerate-probe ``-inf`` padding must survive to the *global*
        post-merge truncation in :meth:`search_batch`, or shards with few
        probed candidates would silently narrow every row. Threads
        execution only (see :meth:`from_bm25`).
        """
        bounds = shard_bounds(backend.size, n_shards)
        views = backend.ivf.shard(n_shards)
        shards = [
            IVFBackend(
                v,
                backend.passages[start:stop] if backend.passages is not None else None,
                n_probe=backend.n_probe,
                truncate_nonfinite=False,
            )
            for v, (start, stop) in zip(views, bounds)
        ]
        return cls(shards, [b[0] for b in bounds], workers=workers)

    @property
    def n_shards(self) -> int:
        """Number of corpus partitions."""
        return len(self.shards)

    @property
    def size(self) -> int:
        """Total corpus passages indexed across every shard."""
        return sum(s.size for s in self.shards)

    # -- search ---------------------------------------------------------------
    def _shard_search(
        self,
        shard_idx: int,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """One shard's ``search_batch`` with ids globalized by its offset."""
        shard = self.shards[shard_idx]
        scores, ids = shard.search_batch(queries, query_vecs, k)
        scores = np.asarray(scores, np.float32)
        ids = np.asarray(ids, np.int32)
        # empty-slot sentinels (id=-1 — BM25's no-match marker, IVF's
        # degenerate-probe padding) are positionless and must never be
        # offset into a neighboring shard's real id range
        ids = np.where(ids >= 0, ids + np.int32(self.offsets[shard_idx]), ids)
        return scores, ids

    def search_batch(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Fan out to every shard, merge per-shard top-k into the global
        top-k.

        Each shard clamps ``k`` to its own row count, so ``k`` larger than a
        shard (or than the whole corpus) degrades exactly like the unsharded
        backend: the merged width is ``min(k, total corpus rows)`` for exact
        shards. Merging uses :func:`~repro.retrieval.topk.merge_topk`
        left-to-right — pure selection over already-computed scores, so no
        arithmetic (and no float drift) happens at merge time.
        """
        if self._pool is not None:
            futures = [
                self._pool.submit(self._shard_search, s, queries, query_vecs, k)
                for s in range(self.n_shards)
            ]
            parts = [f.result() for f in futures]
        else:
            parts = [
                self._shard_search(s, queries, query_vecs, k)
                for s in range(self.n_shards)
            ]
        vals_np, ids_np, n_merges = merge_shard_parts(parts, k)
        self.counters.searches += 1
        self.counters.shard_searches += self.n_shards
        self.counters.merges += n_merges
        return vals_np, ids_np

    # -- payloads -------------------------------------------------------------
    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Resolve global passage ids to payloads via their owning shard."""
        out: list[Passage] = []
        for gid in ids:
            gid = int(gid)
            s = bisect.bisect_right(self.offsets, gid) - 1
            out.extend(self.shards[s].get_passages([gid - self.offsets[s]]))
        return out

    def shutdown(self) -> None:
        """Stop the fan-out thread pool (no-op when running serially)."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)


class DeviceShardedBackend(ShardedBackend):
    """``execution="device"``: S-way sharded MIPS as one ``shard_map``'d
    device program per fixed-width query chunk.

    The corpus (zero-padded to an S-divisible row count) is placed **once**
    across the mesh with the :func:`mesh_layout` corpus spec and stays
    device-resident; every search dispatches the jit'd ``shard_map``
    closure built by ``DenseIndex.sharded_search_fn``. Both are the
    index's (:meth:`DenseIndex.device_sharded_search`), so every backend
    over one index and one mesh — one per engine — shares them: a second
    engine's first search places and compiles nothing. In that program
    per-shard scoring (blocked matmul or the pallas ``mips_topk`` kernel
    with a traced residue mask), local top-k, id globalization by
    ``axis_index * rows_per_shard``, and the cross-shard
    :func:`~repro.retrieval.topk.distributed_topk` merge all execute on
    device. The host only chunks queries into fixed ``(q_block, d)`` blocks
    (default ``Q_BLOCK`` — the same discipline that makes ``DenseIndex``
    batches bit-identical to single queries; benchmarks widen it to
    amortize dispatch overhead) and reassembles rows.

    Compared to the threads path, a search costs one XLA dispatch per query
    chunk instead of S Python dispatches plus S-1 host merges per batch —
    the difference the BENCH_serving.json ``sharding_scaling`` cell
    measures.
    """

    execution = "device"
    # distributed_topk keeps the lowest global id among ties, as top_k does
    nested_topk = True

    def __init__(
        self,
        index: DenseIndex,
        *,
        n_shards: int,
        mesh: jax.sharding.Mesh | None = None,
        scorer: str = "blocked",
        interpret: bool = False,
        name: str | None = None,
        cost: BackendCost | None = None,
        q_block: int | None = None,
    ):
        # shard_bounds is the one validator of (n, S) combinations; calling
        # it here keeps device-path errors identical to the threads path.
        shard_bounds(index.size, n_shards)
        if q_block is not None and q_block < 1:
            raise ValueError(f"q_block must be >= 1, got {q_block}")
        if mesh is None:
            from repro.distributed.mesh_utils import corpus_mesh

            mesh = corpus_mesh(n_shards)
        self.mesh = mesh
        self.shard_axes = tuple(mesh.axis_names)
        mesh_size = int(np.prod([mesh.shape[a] for a in self.shard_axes]))
        if mesh_size != n_shards:
            raise ValueError(f"mesh has {mesh_size} devices but n_shards={n_shards}")
        self.index = index
        self.scorer = scorer
        self.interpret = interpret
        # protocol surface mirrors the threads path's per-shard DenseBackend
        proto = DenseBackend(index, scorer=scorer, interpret=interpret)
        self.name = name if name is not None else proto.name
        self.cost = cost if cost is not None else proto.cost
        self.requires_query_vecs = True
        self.workers = 0
        self._pool = None
        self._n_shards = int(n_shards)
        # Query-chunk width of the fixed-shape dispatch. Q_BLOCK matches the
        # unsharded index's discipline; benchmarks widen it to amortize
        # per-dispatch shard_map overhead over bigger batches (results are
        # bit-identical either way — chunking only tiles the query axis).
        self.q_block = int(q_block) if q_block is not None else Q_BLOCK
        self.counters = ShardCounters()

    @property
    def n_shards(self) -> int:
        return self._n_shards

    @property
    def size(self) -> int:
        return self.index.size

    @property
    def shards(self):  # pragma: no cover - guard against threads-path use
        raise AttributeError(
            "DeviceShardedBackend has no host-side shard backends; the "
            "partitions live on the device mesh"
        )

    @shards.setter
    def shards(self, _value):  # dataclass-free __init__ never sets this
        raise AttributeError("device shards are mesh-resident")

    # -- search ---------------------------------------------------------------
    def search_batch(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched sharded search, bit-identical to the unsharded index.

        Queries are chunked into fixed ``(q_block, d)`` blocks (zero-padded)
        and every chunk dispatches the same compiled shard_map program; all
        chunks are dispatched before any result is read back, so device work
        pipelines across chunks instead of syncing per block.
        """
        if query_vecs is None:
            raise ValueError(f"backend {self.name!r} requires query_vecs")
        k = min(k, self.size)
        q = np.asarray(query_vecs, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_vecs must be (nq, d), got {q.shape}")
        nq = q.shape[0]
        if nq == 0:
            return np.zeros((0, k), np.float32), np.zeros((0, k), np.int32)
        fn, corpus = self.index.device_sharded_search(
            self.mesh, k, self.shard_axes, scorer=self.scorer, interpret=self.interpret
        )
        qb = self.q_block
        pad = (-nq) % qb
        with TraceAnnotation("repro.search", k=k, nq=nq):
            if pad:
                q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)], axis=0)
            outs = []
            for chunk, s in enumerate(range(0, q.shape[0], qb)):
                with TraceAnnotation("repro.search.dispatch", chunk=chunk):
                    outs.append(fn(corpus, jnp.asarray(q[s : s + qb])))
            n_chunks = len(outs)
            vals_np, ids_np = [], []
            for chunk, (v, i) in enumerate(outs):
                with TraceAnnotation("repro.search.fetch", chunk=chunk):
                    vals_np.append(np.asarray(v, np.float32))
                    ids_np.append(np.asarray(i, np.int32))
            vals = np.concatenate(vals_np)[:nq]
            ids = np.concatenate(ids_np)[:nq]
        self.counters.searches += 1
        self.counters.shard_searches += self._n_shards * n_chunks
        self.counters.merges += n_chunks * len(self.shard_axes)
        return vals, ids

    # -- payloads -------------------------------------------------------------
    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Global ids resolve directly against the unsharded payloads — the
        device path never re-homes passages."""
        return self.index.get_passages(ids)

    def shutdown(self) -> None:
        """Nothing to stop: there is no host pool on the device path."""


# --------------------------------------------------------------------------- #
# execution="process": persistent per-shard worker processes                   #
# --------------------------------------------------------------------------- #
def _dense_shard_worker(conn, emb: np.ndarray, scorer: str, interpret: bool) -> None:
    """One shard's resident search service (runs in a spawned process).

    Builds the shard's :class:`DenseIndex`/:class:`DenseBackend` once —
    embeddings arrive already normalized, exactly the slice the threads
    path would take, so scores are bit-identical — then answers
    ``("search", (qvecs, k))`` requests over the pipe until ``("stop",
    None)`` or EOF. Errors are reported as ``("error", repr)`` rather than
    killing the worker: one bad query batch must not wedge the shard.
    """
    from repro.retrieval.backend import DenseBackend
    from repro.retrieval.index import DenseIndex

    backend = DenseBackend(
        DenseIndex(emb, None, assume_normalized=True),
        scorer=scorer,
        interpret=interpret,
    )
    conn.send(("ready", backend.size))
    while True:
        try:
            op, payload = conn.recv()
        except (EOFError, OSError):
            break
        if op == "stop":
            break
        try:
            qvecs, k = payload
            scores, ids = backend.search_batch(None, jnp.asarray(qvecs), k)
            conn.send(
                ("ok", (np.asarray(scores, np.float32), np.asarray(ids, np.int32)))
            )
        except BaseException as err:  # keep serving: report, don't die
            conn.send(("error", f"{type(err).__name__}: {err}"))
    conn.close()


class ProcessShardedBackend(ShardedBackend):
    """``execution="process"``: S-way host fan-out on spawned worker
    processes — the GIL-free counterpart of the threads path.

    Each shard is a persistent child process owning its contiguous slice of
    the (already normalized) corpus embeddings and its own jit search
    closures; a search sends the query block to **all** shards before
    reading any reply, so the S local searches genuinely overlap on S
    cores instead of serializing on the parent's interpreter lock. Ids are
    globalized by shard offset on the parent and merged with the same
    fused :func:`merge_shard_parts` top-k as the threads path, so results
    — and the :class:`ShardCounters` discipline (S ``shard_searches`` and
    S-1 ``merges`` per call) — are bit-identical to it.

    Construction raises :class:`~repro.runtime.AcceleratorHeldError` when
    the default backend is an accelerator: this process then holds the
    chip, and workers that build jax state could not reach it.

    Workers spawn lazily on the first search (``spawn`` context: the
    parent's jax runtime threads make fork unsafe) and each pays one jax
    import + index build; :meth:`warm` fronts that cost. Passage payloads
    resolve against the retained parent index — the workers never see
    them. The live backend holds pipes and processes, so it is
    deliberately not picklable: sending it to a process stage executor
    fails the spawn-safety audit, which is correct — rebuild from config
    in the worker instead.
    """

    execution = "process"
    # dense shard workers, merged lowest id first as on the threads path
    nested_topk = True

    def __init__(
        self,
        index: DenseIndex,
        *,
        n_shards: int,
        scorer: str = "blocked",
        interpret: bool = False,
        name: str | None = None,
        cost: BackendCost | None = None,
    ):
        refuse_children_on_accelerator("shard_execution='process'")
        # shard_bounds is the one validator of (n, S) combinations; calling
        # it here keeps process-path errors identical to the threads path.
        self.bounds = shard_bounds(index.size, n_shards)
        self.offsets = [b[0] for b in self.bounds]
        self.index = index
        self.scorer = scorer
        self.interpret = interpret
        proto = DenseBackend(index, scorer=scorer, interpret=interpret)
        self.name = name if name is not None else proto.name
        self.cost = cost if cost is not None else proto.cost
        self.requires_query_vecs = True
        self.workers = 0
        self._pool = None
        self._n_shards = int(n_shards)
        self.counters = ShardCounters()
        self._procs: list | None = None
        self._conns: list | None = None

    @property
    def n_shards(self) -> int:
        return self._n_shards

    @property
    def size(self) -> int:
        return self.index.size

    @property
    def shards(self):  # pragma: no cover - guard against threads-path use
        raise AttributeError(
            "ProcessShardedBackend has no in-process shard backends; the "
            "partitions live in worker processes"
        )

    @shards.setter
    def shards(self, _value):  # the pipe-based __init__ never sets this
        raise AttributeError("process shards are worker-resident")

    # -- worker lifecycle ------------------------------------------------------
    def _ensure_workers(self) -> None:
        if self._conns is not None:
            return
        ctx = multiprocessing.get_context("spawn")
        emb = np.asarray(self.index.embeddings, np.float32)
        procs, conns = [], []
        for start, stop in self.bounds:
            parent_conn, child_conn = ctx.Pipe()
            p = ctx.Process(
                target=_dense_shard_worker,
                args=(child_conn, emb[start:stop].copy(), self.scorer, self.interpret),
                daemon=True,
            )
            p.start()
            child_conn.close()
            procs.append(p)
            conns.append(parent_conn)
        # all workers spawn concurrently; collect readiness after launching
        for s, c in enumerate(conns):
            tag, payload = c.recv()
            if tag != "ready":  # pragma: no cover - startup failure path
                raise RuntimeError(f"shard {s} worker failed to start: {payload}")
        self._procs, self._conns = procs, conns

    def warm(self) -> None:
        """Spawn the shard workers now (first search pays it otherwise)."""
        self._ensure_workers()

    # -- search ---------------------------------------------------------------
    def search_batch(
        self,
        queries: Sequence[str],
        query_vecs: jnp.ndarray | None,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Fan out to every shard worker, merge per-shard top-k globally.

        Dispatch-then-collect: all S requests are written before any reply
        is read, so shard searches run concurrently across cores.
        """
        if query_vecs is None:
            raise ValueError(f"backend {self.name!r} requires query_vecs")
        self._ensure_workers()
        q = np.asarray(query_vecs, np.float32)
        for conn in self._conns:
            conn.send(("search", (q, int(k))))
        parts: list[tuple[np.ndarray, np.ndarray]] = []
        for s, conn in enumerate(self._conns):
            tag, payload = conn.recv()
            if tag != "ok":
                raise RuntimeError(f"shard {s} worker search failed: {payload}")
            scores, ids = payload
            # sentinels are positionless: never offset them into a
            # neighboring shard's real id range (same rule as _shard_search)
            ids = np.where(ids >= 0, ids + np.int32(self.offsets[s]), ids)
            parts.append((scores, ids))
        vals_np, ids_np, n_merges = merge_shard_parts(parts, k)
        self.counters.searches += 1
        self.counters.shard_searches += self._n_shards
        self.counters.merges += n_merges
        return vals_np, ids_np

    # -- payloads -------------------------------------------------------------
    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Global ids resolve against the retained parent index — payloads
        never cross the worker pipes."""
        return self.index.get_passages(ids)

    def shutdown(self) -> None:
        """Stop the shard workers (idempotent; daemons die with the parent
        anyway, but a clean stop releases their memory immediately)."""
        if self._conns is None:
            return
        for c in self._conns:
            try:
                c.send(("stop", None))
            except (OSError, BrokenPipeError):
                pass
        for c in self._conns:
            c.close()
        for p in self._procs:
            p.join(timeout=10)
        self._procs = self._conns = None
