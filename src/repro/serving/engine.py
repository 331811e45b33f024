"""The CA-RAG serving engine: route → retrieve → generate → log (paper §IV).

One :class:`RAGEngine` wires the whole pipeline:

    1. signal extraction      (core/signals)
    2. utility estimation     (core/utility, + telemetry-refined priors)
    3. bundle selection       (core/router; policy-injected)
    4. retrieval              (retrieval/DenseIndex or HybridRetriever)
    5. generation             (serving/generator)
    6. telemetry logging      (core/telemetry, Appendix-F CSV schema)

plus the §VIII guardrails between 3→4 and 4→5. Every query produces an
auditable QueryRecord; benchmarks read only the CSV artifacts.

The execution pipeline itself lives in :mod:`repro.serving.stages` as five
typed stage functions — ``route → retrieve → assemble → decode → finalize``
— with all shared mutable state (telemetry store, billing ledger, embedder
cache) confined to ``route`` and ``finalize``. The engine's entry points are
thin compositions of those stages and all produce *bit-identical* records:

* :meth:`answer` — one query at a time; the auditable reference path.
* :meth:`answer_batch` — the serving fast path: the whole batch routes in
  one vectorized call, queries group by routed bundle so each group embeds
  once (query-vector cache) and searches once per (bundle, k) through the
  index's cached jit-compiled closures, and a cheap host replay inside
  ``finalize`` recovers position-exact telemetry-refined routing.
  :meth:`run` delegates here, so every caller gets the fast path for free.
* :class:`~repro.serving.stages.StagePipeline` — the N-deep streaming
  executor over the same stages (see serving/streaming.py).
"""

from __future__ import annotations

from typing import Callable, Mapping, Sequence

import dataclasses

import numpy as np

from repro.core.bundles import BundleCatalog, DEFAULT_CATALOG
from repro.retrieval.backend import DenseBackend, RetrievalBackend, make_backends
from repro.core.guardrails import GuardrailConfig, Guardrails
from repro.core.router import Router
from repro.core.telemetry import QueryRecord, TelemetryStore
from repro.core.utility import RealizedNormalization
from repro.retrieval.chunking import line_passages
from repro.retrieval.embedder import CachingEmbedder, Embedder, HashedNGramEmbedder
from repro.retrieval.index import DenseIndex
from repro.serving import stages
from repro.serving.billing import BillingLedger
from repro.serving.generator import ExtractiveGenerator, Generator
from repro.serving.latency import LatencyModel
from repro.serving.scheduler import (
    ContinuousBatchScheduler,
    Rejection,
    Request,
)


class QueueOverflowError(RuntimeError):
    """Scheduler refused part of a batch. Carries the typed
    :class:`~repro.serving.scheduler.Rejection` list (reason + queue depth
    per refused request) so callers can shed load or retry selectively
    instead of parsing the message."""

    def __init__(self, message: str, rejections: list[Rejection]):
        super().__init__(message)
        self.rejections = rejections


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    use_telemetry_refinement: bool = True
    telemetry_min_volume: int = 2
    telemetry_blend: float = 0.35
    # Start from the engine's structural latency/cost predictions instead of
    # the naive Table-I priors (used for the weight-sensitivity analysis,
    # where the operator tunes weights with knowledge of the deployed
    # system's behaviour — paper §VIII.D):
    warm_start_telemetry: bool = False
    guardrails: GuardrailConfig = GuardrailConfig()
    realized_norm: RealizedNormalization = RealizedNormalization()


@dataclasses.dataclass
class EngineResponse:
    answer: str
    record: QueryRecord
    passages: list[str]


class RAGEngine:
    def __init__(
        self,
        router: Router,
        index: DenseIndex,
        embedder: Embedder,
        generator: Generator | None = None,
        latency_model: LatencyModel | None = None,
        *,
        catalog: BundleCatalog = DEFAULT_CATALOG,
        config: EngineConfig = EngineConfig(),
        index_embedding_tokens: int = 0,
        backends: Mapping[str, RetrievalBackend] | None = None,
    ):
        self.router = router
        self.index = index
        # Pluggable retrieval: bundle.backend names a RetrievalBackend here.
        # Default is the dense adapter over `index` — a pure delegation, so
        # a dense-only (paper) catalog serves bit-identical records whether
        # or not the caller ever heard of backends.
        self.backends: dict[str, RetrievalBackend] = (
            dict(backends) if backends is not None else {}
        )
        self.backends.setdefault("dense", DenseBackend(index))
        missing = [b for b in catalog.backends_used() if b not in self.backends]
        if missing:
            raise ValueError(
                f"catalog routes through backends {missing} but the engine only "
                f"has {sorted(self.backends)}; build them with "
                "repro.retrieval.backend.make_backends and pass backends=..."
            )
        # Query-vector cache: repeated queries skip the embed stage entirely
        # (compute only — τ_embed billing stays per call, Eq. 2).
        self.embedder = (
            embedder if isinstance(embedder, CachingEmbedder) else CachingEmbedder(embedder)
        )
        self.generator = generator or ExtractiveGenerator()
        self.latency_model = latency_model or LatencyModel()
        self.catalog = catalog
        self.config = config
        struct_lat, struct_cost = self._structural_predictions()
        self.telemetry = TelemetryStore(
            catalog,
            min_volume=config.telemetry_min_volume,
            blend=config.telemetry_blend,
            structural_latency=struct_lat,
            structural_cost=struct_cost,
        )
        self.guardrails = Guardrails(catalog, config.guardrails)
        self.ledger = BillingLedger(index_embedding_tokens)
        self._query_counter = 0
        # what answer_batch served: queries routed and replayed, searches
        self.counts = stages.StageCounts()

    # ------------------------------------------------------------------ #
    def _structural_predictions(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-bundle end-to-end (latency_ms, billed_tokens) predicted from
        the engine's own latency model + prompt-template token structure.

        This is what a production deployment calibrates before launch; the
        telemetry EMAs then correct residual modeling error (§IV.A step 2).
        """
        base_prompt = 28  # grounded template + question tokens
        direct_prompt = 16
        tokens_per_passage = 19  # corpus line + citation tag
        embed_tokens = 8
        grounded_completion = 80  # context-constrained answers
        direct_completion = 170  # unconstrained answers run long (§VII.B)
        lat, cost = [], []
        for b in self.catalog:
            # validation guarantees a backend for every retrieval bundle;
            # skip_retrieval bundles never touch one (scale is moot at k=0)
            backend = self.backends.get(b.backend)
            if b.skip_retrieval:
                prompt = direct_prompt
                completion = direct_completion
                emb = 0
            else:
                prompt = base_prompt + tokens_per_passage * b.top_k
                # BM25-style backends never spend the embed call
                emb = embed_tokens if backend.requires_query_vecs else 0
                completion = grounded_completion
            stages_ms = self.latency_model.stages_ms(
                embed_tokens=emb,
                retrieval_k=b.top_k,
                prompt_tokens=prompt,
                completion_tokens=completion,
                # `is not None`, never truthiness: container-like backends
                # (CachedBackend defines __len__) are falsy while empty
                retrieval_latency_scale=(
                    backend.cost.latency_scale if backend is not None else 1.0
                ),
            )
            lat.append(sum(stages_ms.values()))
            cost.append(prompt + completion + emb)
        return np.asarray(lat, np.float64), np.asarray(cost, np.float64)

    def _priors(self, telemetry: TelemetryStore | None = None):
        """Refined (latency, cost, recall) prior vectors from a telemetry
        store — the live store by default, or a replay clone (the finalize
        stage). The recall vector is ``None`` until some backend clears the
        store's min-sample threshold (``refined_recall_priors``), which
        keeps unobserved catalogs routing on the static curve bit-exactly.
        """
        store = telemetry if telemetry is not None else self.telemetry
        if not self.config.use_telemetry_refinement:
            return None, None, None
        recall = store.refined_recall_priors()
        if recall is not None:
            recall = recall.astype(np.float32)
        if self.config.warm_start_telemetry and not store.refinement_active:
            return (
                np.asarray(store.structural_latency, np.float32),
                np.asarray(store.structural_cost, np.float32),
                recall,
            )
        return (
            store.refined_latency_priors().astype(np.float32),
            store.refined_cost_priors().astype(np.float32),
            recall,
        )

    def calibrate_backend_recall(
        self,
        queries: Sequence[str],
        *,
        backends: Sequence[str] | None = None,
        k: int | None = None,
    ) -> dict[str, float]:
        """Measure each backend's recall@k against exact dense retrieval and
        log the observations into the telemetry store.

        This is the live counterpart of the static ``BackendCost.recall_prior``
        curve: per query, the overlap between a backend's returned ids and
        the exact dense backend's top-k becomes one
        :meth:`~repro.core.telemetry.TelemetryStore.observe_recall` sample.
        Once a backend clears ``recall_min_samples``, routing consumes the
        shrunk refined prior instead of the static curve
        (docs/retrieval.md#calibrating-recall-priors-from-telemetry).

        ``backends`` defaults to every non-dense backend the catalog routes
        through; ``k`` defaults per backend to the deepest ``top_k`` among
        its bundles. Returns the mean measured recall per backend.

        Degraded measurements never reach the store: a backend whose
        decorator stack injects faults (``faults.FaultyBackend`` — its rows
        may be fabricated empty/truncated sets) or whose resilient wrapper
        reports it unavailable mid-calibration yields ``NaN`` with **zero**
        ``observe_recall`` observations, so injected chaos cannot corrupt
        the refined recall priors routing consumes.
        """
        from repro.retrieval.faults import has_injected_faults
        from repro.serving.resilience import BackendUnavailableError

        queries = list(queries)
        if not queries:
            raise ValueError("need at least one calibration query")
        targets = list(
            backends
            if backends is not None
            else [b for b in self.catalog.backends_used() if b != "dense"]
        )
        unknown = [t for t in targets if t not in self.backends]
        if unknown:
            raise ValueError(f"unknown backends {unknown}; have {sorted(self.backends)}")
        import jax.numpy as jnp

        dense = self.backends["dense"]
        vecs = np.asarray(self.embedder.embed(queries), np.float32)
        vec_mat = jnp.asarray(vecs)
        exact_by_k: dict[int, np.ndarray] = {}  # the expensive search, once per k
        out: dict[str, float] = {}
        for name in targets:
            backend = self.backends[name]
            if has_injected_faults(backend):
                out[name] = float("nan")
                continue
            kk = k
            if kk is None:
                depths = [
                    b.top_k
                    for b in self.catalog
                    if b.backend == name and not b.skip_retrieval
                ]
                kk = max(depths) if depths else 5
            kk = min(kk, dense.size)
            exact_ids = exact_by_k.get(kk)
            if exact_ids is None:
                _, exact_ids = dense.search_batch(queries, vec_mat, kk)
                exact_by_k[kk] = exact_ids
            try:
                _, ids = backend.search_batch(
                    queries, vec_mat if backend.requires_query_vecs else None, kk
                )
            except BackendUnavailableError:
                out[name] = float("nan")
                continue
            exact_np, ids_np = np.asarray(exact_ids), np.asarray(ids)
            recalls = []
            for i in range(len(queries)):
                exact_row = set(exact_np[i].tolist())
                hit = len(exact_row & set(ids_np[i].tolist()))
                r = hit / max(len(exact_row), 1)
                self.telemetry.observe_recall(name, r)
                recalls.append(r)
            out[name] = float(np.mean(recalls))
        return out

    # ------------------------------------------------------------------ #
    # Entry points: thin compositions of the five stages                   #
    # ------------------------------------------------------------------ #
    def answer(self, query: str, *, reference: str | None = None) -> EngineResponse:
        """One query through the full stage chain (the reference path —
        a single-element :meth:`answer_batch`, bit-identical records)."""
        return self.answer_batch([query], [reference])[0]

    def answer_batch(
        self, queries: Sequence[str], references: Sequence[str] | None = None
    ) -> list[EngineResponse]:
        """Serve a whole batch through the vectorized fast path.

        Produces records bit-identical to ``[self.answer(q) for q in
        queries]`` — the parity the serving tests pin down — at a fraction of
        the dispatch cost: one routing call per micro-batch instead of one
        per query, one embed call per k group's cache misses, and one
        compiled search call per (bundle, k) group instead of one per query.
        The body is literally the five stages composed in order.
        """
        n = len(queries)
        if n == 0:
            return []
        refs = list(references) if references is not None else [None] * n
        if len(refs) != n:
            raise ValueError(f"{n} queries but {len(refs)} references")
        n_records = len(self.telemetry.records)
        routed = stages.route(self, queries, refs)
        try:
            retrieved = stages.retrieve(self, routed)
            admitted = stages.assemble(self, retrieved)
            decoded = stages.decode(self, admitted)
            responses = stages.finalize(self, decoded)
        except BaseException:
            # route() allocated the batch's query ids up front (so pipelined
            # callers can keep routing while earlier batches finalize). In
            # this inline composition nothing else can have allocated since,
            # so if the batch died before committing any record, return the
            # ids — latency noise is seeded per query_id, and leaking ids on
            # a recoverable error would silently shift every later record.
            if (
                len(self.telemetry.records) == n_records
                and self._query_counter == routed.qid0 + n
            ):
                self._query_counter = routed.qid0
            raise
        self.counts.add(decoded)
        return responses

    # ------------------------------------------------------------------ #
    # Batch entry points                                                   #
    # ------------------------------------------------------------------ #
    def run(self, queries: Sequence[str], references: Sequence[str] | None = None) -> TelemetryStore:
        """Run a query stream through the batched fast path (bit-identical
        to the sequential loop it replaces)."""
        self.answer_batch(list(queries), references)
        return self.telemetry

    def serve_batch(
        self,
        queries: Sequence[str],
        references: Sequence[str] | None = None,
        *,
        scheduler: ContinuousBatchScheduler | None = None,
        decode_fn: Callable[[list[Request]], list[bool]] | None = None,
        max_steps: int = 100_000,
    ) -> tuple[list[EngineResponse], ContinuousBatchScheduler]:
        """Closed loop: routing → admission → decode.

        Routes/retrieves/generates the batch through :meth:`answer_batch`,
        converts the finalized records into scheduler :class:`Request`s (the
        routed bundle fixes each request's queue, prompt length, and decode
        budget — :meth:`ContinuousBatchScheduler.make_requests`), feeds the
        :class:`ContinuousBatchScheduler`, and drains it — so router
        decisions drive continuous-batching admission and decode directly.
        Returns (responses, scheduler); scheduler.summary() carries the
        queue-wait / decode-step telemetry a deployment feeds back into
        routing.
        """
        responses = self.answer_batch(queries, references)
        scheduler = scheduler or ContinuousBatchScheduler(catalog=self.catalog)
        reqs = scheduler.make_requests([r.record for r in responses])
        n_rej_before = len(scheduler.rejections)
        accepted = scheduler.submit_many(reqs)
        if accepted < len(reqs):
            raise QueueOverflowError(
                f"scheduler accepted {accepted}/{len(reqs)} requests (queue cap "
                f"{scheduler.config.max_queue}, page pool {scheduler.config.n_pages}); "
                "drain the scheduler, raise its capacity, or submit smaller batches",
                rejections=scheduler.rejections[n_rej_before:],
            )
        decode_fn = decode_fn or (lambda active: [False] * len(active))
        scheduler.run_until_drained(decode_fn, max_steps=max_steps)
        return responses, scheduler


def build_paper_engine(
    policy_router: Router,
    *,
    embed_dim: int = 256,
    config: EngineConfig = EngineConfig(),
    stack: "BackendStackConfig | None" = None,
) -> RAGEngine:
    """Engine wired to the paper's benchmark corpus (Appendix E).

    Builds every retrieval backend the router's catalog routes through
    (``catalog.backends_used()``) over the shared corpus — the paper
    catalog needs only the dense index; the extended catalog adds BM25 /
    IVF / hybrid adapters deterministically (seeded IVF k-means).

    ``stack`` optionally dresses the backend map through
    :func:`repro.retrieval.build_backend_stack` (shard → faults → cache →
    resilience) — the declarative equivalent of hand-wrapping
    ``engine.backends`` after construction."""
    from repro.data.benchmark import corpus_document

    embedder = HashedNGramEmbedder(dim=embed_dim)
    passages = line_passages(corpus_document())
    index, index_tokens = DenseIndex.build(passages, embedder)
    catalog = policy_router.catalog
    backends = make_backends(
        index, passages, embedder, names=("dense", *catalog.backends_used())
    )
    if stack is not None:
        from repro.retrieval import build_backend_stack

        backends = build_backend_stack(backends, stack, index=index)
    return RAGEngine(
        policy_router,
        index,
        embedder,
        catalog=catalog,
        config=config,
        index_embedding_tokens=index_tokens,
        backends=backends,
    )
