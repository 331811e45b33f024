"""Typed serving stages: route → retrieve → assemble → decode → finalize.

The engine's route→retrieve→generate→log loop (paper §IV) decomposed into
five stage functions over explicit artifact dataclasses. Each artifact
carries everything the next stage needs, so a stage never reaches back into
the engine for per-query state:

    route(queries)            -> RoutedBatch      (qids, priors, speculation)
    retrieve(RoutedBatch)     -> RetrievedBatch   (searches grouped by (backend, k))
    assemble(RetrievedBatch)  -> AdmittedBatch    (guardrails + prompt build)
    decode(AdmittedBatch)     -> DecodedBatch     (generation, billing, latency)
    finalize(DecodedBatch)    -> list[EngineResponse]  (replay, ledger, telemetry)

Shared-state discipline — what makes the pipeline safe to deepen:

* ``route`` and ``finalize`` are the only stages that touch shared mutable
  engine state. ``route`` stamps query ids and warms the query-vector cache;
  ``finalize`` runs the exact-replay pass and commits billing + telemetry.
  Callers must invoke them serially, in arrival order.
* ``retrieve``, ``assemble``, and ``decode`` are side-effect-free given
  their input artifact: the caches they touch (compiled search closures,
  passage term sets, latency noise factors) are idempotent memos, so calling
  a stage twice on the same artifact yields equal outputs and mutates no
  telemetry or billing state. They may run on worker threads, and different
  micro-batches may occupy different stages concurrently — the N-deep
  pipelining :class:`StagePipeline` exploits.

Exactness at any depth: speculation in ``route`` may use stale telemetry
priors (a deep pipeline routes micro-batch b before b-1 has finalized), but
``finalize`` replays the telemetry stream position by position on a clone
(:meth:`TelemetryStore.clone_for_replay`) and re-executes any query whose
true-prior routing differs, so drained records are bit-identical to the
sequential loop at every (pipeline_depth, retrieval_workers) setting.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as futures_wait
from typing import TYPE_CHECKING, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.telemetry import QueryRecord
from repro.core.utility import realized_utility
from repro.retrieval.faults import RetrievalFault
from repro.retrieval.tokenizer import lexical_overlap
from repro.serving.billing import TokenBill, bill_query
from repro.serving.generator import build_prompt
from repro.serving.resilience import (
    BackendUnavailableError,
    ResilienceEvents,
    degradation_ladder,
)
from repro.training.fault_tolerance import HeartbeatMonitor

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (engine imports us)
    from repro.serving.engine import EngineResponse, RAGEngine


# --------------------------------------------------------------------------- #
# Stage artifacts                                                              #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Execution:
    """Everything downstream of a (query, guarded-bundle) decision.

    Deterministic given (query_id, query, guarded bundle index), so the
    replay pass caches executions across speculation rounds.
    """

    final_bundle_idx: int
    passages: list[str]
    confidence: float
    answer: str
    prompt: str
    bill: TokenBill
    latency_ms: float
    quality: float
    # resilience outcome: True when this answer came off-plan via the
    # degradation ladder (fallback_depth = rungs walked to reach it)
    degraded: bool = False
    fallback_depth: int = 0


@dataclasses.dataclass
class RoutedBatch:
    """Output of :func:`route`: the speculative routing plan for one
    micro-batch, with the query vectors the retrieve stage will search."""

    qid0: int
    queries: list[str]
    references: list[str | None]
    complexity: np.ndarray  # (n,) float
    choices: np.ndarray  # (n,) int32 — speculative routed bundle per query
    utilities: np.ndarray  # (n, B) — Eq. 1 utilities under route-time priors
    guarded: list[int]  # pre-execution guardrail outcome per query
    retrieval_plan: dict[tuple[str, int], list[int]]  # (backend, top_k) → positions
    query_vecs: dict[int, np.ndarray]  # position → (d,) embedded query (vec backends only)
    refinement_on: bool

    @property
    def n(self) -> int:
        """Number of queries in this micro-batch."""
        return len(self.queries)


@dataclasses.dataclass
class RetrievedBatch:
    """Output of :func:`retrieve`: per-position (scores, ids) rows from the
    backend-grouped batched searches."""

    routed: RoutedBatch
    retrievals: dict[int, tuple[np.ndarray, np.ndarray]]  # position → (k,) rows
    search_calls: int  # search_batch invocations (one per (backend, k) group)
    search_calls_by_backend: dict[str, int] = dataclasses.field(default_factory=dict)
    # per-backend cache hit/miss/eviction deltas incurred by this batch's
    # searches (CachedBackend-wrapped backends only; empty otherwise)
    cache_events: dict[str, dict[str, int]] = dataclasses.field(default_factory=dict)
    # degradation-ladder outcomes: position → bundle index actually served
    # (only positions whose planned backend was unavailable) and the number
    # of ladder rungs walked to get there
    fallback_bundle: dict[int, int] = dataclasses.field(default_factory=dict)
    fallback_depth: dict[int, int] = dataclasses.field(default_factory=dict)
    # typed resilience counters for this batch's searches (retries, timeouts,
    # breaker transitions, ladder outcomes — serving/resilience.py)
    resilience: ResilienceEvents = dataclasses.field(default_factory=ResilienceEvents)


@dataclasses.dataclass
class AdmittedBatch:
    """Output of :func:`assemble`: guardrail-final bundles, fetched passages,
    and built prompts — everything generation needs, no index access left."""

    retrieved: RetrievedBatch
    final_bundle: list[int]  # post-retrieval-guardrail bundle per query
    passages: list[list[str]]
    confidences: list[float]
    prompts: list[str]
    embedded: list[bool]  # did this query spend an embed call (billing)

    @property
    def routed(self) -> RoutedBatch:
        """The originating routing artifact (convenience accessor)."""
        return self.retrieved.routed


@dataclasses.dataclass
class DecodedBatch:
    """Output of :func:`decode`: full executions for the speculative plan,
    keyed for reuse by the replay pass in :func:`finalize`."""

    admitted: AdmittedBatch
    executions: list[Execution]
    exec_cache: dict[tuple[int, int], Execution]  # (position, guarded idx)
    search_calls: int  # retrieve-stage calls; finalize adds replay searches
    search_calls_by_backend: dict[str, int] = dataclasses.field(default_factory=dict)
    cache_events: dict[str, dict[str, int]] = dataclasses.field(default_factory=dict)
    resilience: ResilienceEvents = dataclasses.field(default_factory=ResilienceEvents)
    replayed: int = 0  # queries finalize re-executed under their true priors

    @property
    def routed(self) -> RoutedBatch:
        """The originating routing artifact (convenience accessor)."""
        return self.admitted.routed


def merge_cache_events(
    total: dict[str, dict[str, int]], events: "Mapping[str, Mapping[str, int]]"
) -> None:
    """Accumulate per-backend cache counter dicts into ``total`` in place.

    The single accumulation point for cache observability — the retrieve
    stage, the finalize replay merge, and the :class:`StagePipeline` all
    fold deltas through here, so a new counter field propagates everywhere
    by appearing in :meth:`~repro.retrieval.cache.CacheStats.as_dict`.
    """
    for bname, ev in events.items():
        tot = total.setdefault(bname, {})
        for key, v in ev.items():
            tot[key] = tot.get(key, 0) + v


def _fold_searches(total, part) -> None:
    """Add ``part``'s search, cache and resilience counters into ``total``
    (a :class:`DecodedBatch` or :class:`StageCounts`) in place."""
    total.search_calls += part.search_calls
    by = total.search_calls_by_backend
    for bname, cnt in part.search_calls_by_backend.items():
        by[bname] = by.get(bname, 0) + cnt
    merge_cache_events(total.cache_events, part.cache_events)
    total.resilience.add(part.resilience)


@dataclasses.dataclass
class StageCounts:
    """Counters summed over finalized batches: what the engine's
    ``answer_batch`` and a :class:`StagePipeline` each served."""

    routed: int = 0  # queries routed
    replayed: int = 0  # of those, re-executed by finalize's replay pass
    search_calls: int = 0  # search_batch calls, replays included
    search_calls_by_backend: dict[str, int] = dataclasses.field(default_factory=dict)
    # per-backend cache hit/miss/eviction totals (CachedBackend only)
    cache_events: dict[str, dict[str, int]] = dataclasses.field(default_factory=dict)
    # typed resilience totals (retries/timeouts/breaker/ladder outcomes)
    resilience: ResilienceEvents = dataclasses.field(default_factory=ResilienceEvents)

    def add(self, decoded: DecodedBatch) -> None:
        """Fold one finalized batch's counters in."""
        self.routed += decoded.routed.n
        self.replayed += decoded.replayed
        _fold_searches(self, decoded)


def _batch_span(stage):
    """Run a stage inside the profiler span ``repro.<stage>``, whose ids
    (the batch's first query id and size) its other stages' spans share.
    With the profiler off a span costs about a microsecond."""
    name = "repro." + stage.__name__

    @functools.wraps(stage)
    def traced(engine: "RAGEngine", artifact):
        routed = artifact if isinstance(artifact, RoutedBatch) else artifact.routed
        with TraceAnnotation(name, qid0=routed.qid0, n=routed.n):
            return stage(engine, artifact)

    return traced


# --------------------------------------------------------------------------- #
# Per-query execution core (shared by decode and the replay pass)              #
# --------------------------------------------------------------------------- #
def execute_one(
    engine: "RAGEngine",
    qid: int,
    query: str,
    routed_idx: int,
    reference: str | None,
) -> DecodedBatch:
    """Run one routed query through retrieve → assemble → decode.

    The replay path's single-query execution. It *is* the batched middle
    stages applied to a one-element plan — not a re-implementation — so it
    can never drift from what the pipeline computed for the speculative
    choices. Embeds on the caller's thread (only ``route``/``finalize`` may
    call this: the embedder cache is confined to those boundaries).

    Returns the one-element :class:`DecodedBatch` (execution at index 0),
    so the caller can also merge its search/cache counters into the
    enclosing batch's totals.
    """
    guarded = engine.guardrails.pre_execution(int(routed_idx)).bundle_index
    bundle = engine.catalog[guarded]
    plan: dict[tuple[str, int], list[int]] = {}
    qvecs: dict[int, np.ndarray] = {}
    if not bundle.skip_retrieval:
        if engine.backends[bundle.backend].requires_query_vecs:
            with TraceAnnotation("repro.embed", n=1):
                qvecs[0] = np.asarray(engine.embedder.embed([query]), np.float32)[0]
        plan[(bundle.backend, bundle.top_k)] = [0]
    routed = RoutedBatch(
        qid0=qid,
        queries=[query],
        references=[reference],
        complexity=np.zeros((1,), np.float64),
        choices=np.asarray([routed_idx], np.int32),
        utilities=np.zeros((1, 1), np.float64),
        guarded=[guarded],
        retrieval_plan=plan,
        query_vecs=qvecs,
        refinement_on=False,
    )
    return decode(engine, assemble(engine, retrieve(engine, routed)))


def make_record(
    engine: "RAGEngine",
    qid: int,
    query: str,
    ex: Execution,
    utility: float,
    realized: float,
    *,
    complexity: float = 0.0,
) -> QueryRecord:
    """Build the Appendix-F row for one execution."""
    bundle = engine.catalog[ex.final_bundle_idx]
    return QueryRecord(
        query=query,
        strategy=bundle.name,
        bundle=bundle.name,
        utility=utility,
        quality_proxy=ex.quality,
        realized_utility=realized,
        latency=ex.latency_ms,
        prompt_tokens=ex.bill.prompt_tokens,
        completion_tokens=ex.bill.completion_tokens,
        embedding_tokens=ex.bill.embedding_tokens,
        retrieval_confidence=ex.confidence,
        complexity_score=complexity,
        index_embedding_tokens=engine.ledger.index_embedding_tokens if qid == 0 else 0,
        degraded=ex.degraded,
        fallback_depth=ex.fallback_depth,
    )


# --------------------------------------------------------------------------- #
# Stage 1: route (mutates: query counter, embedder cache)                      #
# --------------------------------------------------------------------------- #
def route(
    engine: "RAGEngine",
    queries: Sequence[str],
    references: Sequence[str | None],
) -> RoutedBatch:
    """Signals → priors → speculative vectorized routing → query embedding.

    The only entry stage: stamps query ids (so pipelined micro-batches keep
    arrival-ordered qids even before earlier batches finalize) and embeds the
    queries the speculative plan will retrieve for (one embed call per k
    group, through the engine's query-vector cache). Must be called serially
    in arrival order.
    """
    queries = list(queries)
    with TraceAnnotation("repro.route", qid0=engine._query_counter, n=len(queries)):
        refs = list(references)
        n = len(queries)
        qid0 = engine._query_counter

        cplx_np = np.asarray(engine.router.complexity_batch(queries))
        lat0, cost0, rec0 = engine._priors()
        choices, util_np = engine.router.route_batch_np(
            cplx_np, latency_override=lat0, cost_override=cost0, recall_override=rec0
        )

        guarded = [engine.guardrails.pre_execution(int(c)).bundle_index for c in choices]
        plan: dict[tuple[str, int], list[int]] = {}
        for i in range(n):
            bundle = engine.catalog[guarded[i]]
            if not bundle.skip_retrieval:
                plan.setdefault((bundle.backend, bundle.top_k), []).append(i)
        query_vecs: dict[int, np.ndarray] = {}
        for (bname, _k), idxs in plan.items():
            if not engine.backends[bname].requires_query_vecs:
                continue  # lexical backends never spend the embed call
            with TraceAnnotation("repro.embed", n=len(idxs)):
                vecs = np.asarray(engine.embedder.embed([queries[i] for i in idxs]), np.float32)
            for r, i in enumerate(idxs):
                query_vecs[i] = vecs[r]

        # Allocate the ids only once nothing in this stage can fail: a routing
        # or embedding error must not leak qids (latency noise and generator
        # verbosity are seeded per query_id, so a leak would shift every later
        # record off the reference stream). route is contractually serial, so
        # deferring the increment cannot race a concurrent allocation.
        engine._query_counter += n

        return RoutedBatch(
            qid0=qid0,
            queries=queries,
            references=refs,
            complexity=cplx_np,
            choices=choices,
            utilities=util_np,
            guarded=guarded,
            retrieval_plan=plan,
            query_vecs=query_vecs,
            refinement_on=lat0 is not None,
        )


# --------------------------------------------------------------------------- #
# Stage 2: retrieve (pure)                                                     #
# --------------------------------------------------------------------------- #
def _search_group(
    engine: "RAGEngine",
    bname: str,
    k: int,
    idxs: list[int],
    routed: RoutedBatch,
    cache_events: dict[str, dict[str, int]],
    events: ResilienceEvents,
) -> tuple[np.ndarray, np.ndarray]:
    """One batched search for positions ``idxs`` on backend ``bname``.

    Prefers the backend's telemetry-bearing entry points —
    ``search_batch_resilient`` (ResilientBackend: resilience events + inner
    cache delta) over ``search_batch_stats`` (CachedBackend: cache delta)
    over plain ``search_batch`` — and folds the deltas into the batch
    accumulators. Raises the :class:`~repro.retrieval.faults.RetrievalFault`
    family when the backend is unhealthy (events already merged).
    """
    backend = engine.backends[bname]
    qtexts = [routed.queries[i] for i in idxs]
    qmat = (
        jnp.asarray(np.stack([routed.query_vecs[i] for i in idxs]))
        if backend.requires_query_vecs
        else None
    )
    res_fn = getattr(backend, "search_batch_resilient", None)
    if res_fn is not None:
        try:
            scores, ids, ev, cdelta = res_fn(qtexts, qmat, k)
        except BackendUnavailableError as err:
            events.add(err.events)
            raise
        events.add(ev)
        merge_cache_events(cache_events, cdelta)
    else:
        stats_fn = getattr(backend, "search_batch_stats", None)
        if stats_fn is not None:
            scores, ids, delta = stats_fn(qtexts, qmat, k)
            merge_cache_events(cache_events, {bname: delta.as_dict()})
        else:
            scores, ids = backend.search_batch(qtexts, qmat, k)
    return np.asarray(scores, np.float32), np.asarray(ids, np.int32)


def _degrade_group(
    engine: "RAGEngine",
    routed: RoutedBatch,
    idxs: list[int],
    retrievals: dict[int, tuple[np.ndarray, np.ndarray]],
    fallback_bundle: dict[int, int],
    fallback_depth: dict[int, int],
    cache_events: dict[str, dict[str, int]],
    events: ResilienceEvents,
    calls_by: dict[str, int],
) -> int:
    """Walk the degradation ladder for one failed (backend, k) group.

    Positions are regrouped by their routed (guarded) bundle — groups can
    merge bundles that share (backend, k) — and each sub-group walks
    :func:`~repro.serving.resilience.degradation_ladder` until a rung
    answers. Retrieval rungs re-enter the normal search path (so a wrapped
    rung backend gets its own retry/breaker discipline, and its cache/
    resilience deltas land in the same accumulators); the terminal
    retrieval-free rung cannot fail, so every position resolves — tagged in
    ``fallback_bundle``/``fallback_depth`` and counted as ``degraded``.

    Ladder rungs never embed: ``route`` confined embedding to the
    route/finalize threads, so a rung requiring query vectors is usable only
    when the original plan already embedded these positions (always true
    when the failed backend was itself a vector backend).

    Returns the number of successful rung searches (the caller's
    ``search_calls`` delta). Raises :class:`BackendUnavailableError` only if
    the catalog has no viable rung at all — no retrieval-free bundle.
    """
    calls = 0
    by_bundle: dict[int, list[int]] = {}
    for i in idxs:
        by_bundle.setdefault(routed.guarded[i], []).append(i)
    for bidx, sub in by_bundle.items():
        depth_reached = 0
        resolved = False
        for depth, cand_idx in enumerate(degradation_ladder(engine.catalog, bidx), start=1):
            depth_reached = depth
            cand = engine.catalog[cand_idx]
            if cand.skip_retrieval:
                for i in sub:
                    fallback_bundle[i] = cand_idx
                    fallback_depth[i] = depth
                events.fallbacks += 1
                resolved = True
                break
            cand_backend = engine.backends.get(cand.backend)
            if cand_backend is None:
                continue
            if cand_backend.requires_query_vecs and any(
                i not in routed.query_vecs for i in sub
            ):
                continue
            events.fallbacks += 1
            try:
                scores_np, ids_np = _search_group(
                    engine, cand.backend, cand.top_k, sub, routed, cache_events, events
                )
            except RetrievalFault:
                continue
            calls += 1
            calls_by[cand.backend] = calls_by.get(cand.backend, 0) + 1
            for r, i in enumerate(sub):
                retrievals[i] = (scores_np[r], ids_np[r])
                fallback_bundle[i] = cand_idx
                fallback_depth[i] = depth
            resolved = True
            break
        if not resolved:
            raise BackendUnavailableError(
                f"bundle {engine.catalog[bidx].name!r} has no viable degradation "
                "rung (catalog lacks a retrieval-free bundle and every retrieval "
                "rung is unavailable)",
                events=events,
            )
        events.degraded += len(sub)
        events.fallback_depth_total += depth_reached * len(sub)
    return calls


@_batch_span
def retrieve(engine: "RAGEngine", routed: RoutedBatch) -> RetrievedBatch:
    """Backend-grouped search: one batched ``search_batch`` call per
    (backend, k) group — the dense groups hit the compiled MIPS closures,
    lexical/approximate groups their own batched paths.

    Pure — reads only the immutable backends (and their idempotent
    compiled/LRU caches: a :class:`~repro.retrieval.cache.CachedBackend` hit
    returns bit-identical rows, so caching never perturbs results); safe to
    run on a worker thread concurrently with other micro-batches' stages.
    Cache-wrapped backends report their per-call hit/miss/eviction deltas
    through the artifact's ``cache_events`` (the counters the streaming
    summary surfaces as ``backend_cache``).

    Fault tolerance: a group whose backend raises the
    :class:`~repro.retrieval.faults.RetrievalFault` family (a
    :class:`~repro.serving.resilience.ResilientBackend` that exhausted its
    retries, an open circuit breaker, or a raw injected fault) does **not**
    kill the micro-batch — its positions walk the catalog-derived
    degradation ladder (:func:`_degrade_group`) and resolve to a cheaper
    backend, a shallower depth, or the retrieval-free direct bundle, tagged
    ``degraded`` in the artifact. Any *other* exception type is a
    programming error and propagates (the pipeline wraps it in
    :class:`StageError`).
    """
    retrievals: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    calls = 0
    calls_by: dict[str, int] = {}
    cache_events: dict[str, dict[str, int]] = {}
    events = ResilienceEvents()
    fallback_bundle: dict[int, int] = {}
    fallback_depth: dict[int, int] = {}
    for (bname, k), idxs in routed.retrieval_plan.items():
        try:
            scores_np, ids_np = _search_group(
                engine, bname, k, idxs, routed, cache_events, events
            )
        except RetrievalFault:
            calls += _degrade_group(
                engine,
                routed,
                idxs,
                retrievals,
                fallback_bundle,
                fallback_depth,
                cache_events,
                events,
                calls_by,
            )
            continue
        calls += 1
        calls_by[bname] = calls_by.get(bname, 0) + 1
        for r, i in enumerate(idxs):
            retrievals[i] = (scores_np[r], ids_np[r])
    return RetrievedBatch(
        routed=routed,
        retrievals=retrievals,
        search_calls=calls,
        search_calls_by_backend=calls_by,
        cache_events=cache_events,
        fallback_bundle=fallback_bundle,
        fallback_depth=fallback_depth,
        resilience=events,
    )


# --------------------------------------------------------------------------- #
# Stage 3: assemble (pure) — guardrails + passage fetch + prompt build         #
# --------------------------------------------------------------------------- #
@_batch_span
def assemble(engine: "RAGEngine", retrieved: RetrievedBatch) -> AdmittedBatch:
    """Post-retrieval guardrails (low-confidence demotion), passage payload
    fetch, and prompt construction. Pure given the artifact.

    Positions the retrieve stage degraded assemble under their *fallback*
    bundle (``retrieved.fallback_bundle``): passages come from the rung
    backend that actually answered, and the confidence guardrail applies at
    that bundle — a degraded answer still gets demoted to direct inference
    when its fallback retrieval looks unconvincing.
    """
    routed = retrieved.routed
    final_bundle: list[int] = []
    passages_all: list[list[str]] = []
    confidences: list[float] = []
    prompts: list[str] = []
    embedded: list[bool] = []
    for i in range(routed.n):
        bundle_idx = retrieved.fallback_bundle.get(i, routed.guarded[i])
        bundle = engine.catalog[bundle_idx]
        passages: list[str] = []
        confidence = float("nan")
        # retrieval and embedding are now distinct spends: a lexical backend
        # retrieves without ever embedding (billing reads `embedded`)
        did_embed = i in routed.query_vecs
        if not bundle.skip_retrieval:
            scores, ids = retrieved.retrievals[i]
            confidence = float(scores[0]) if scores.size else float("nan")
            post = engine.guardrails.post_retrieval(bundle_idx, confidence)
            if post.demoted:
                bundle_idx = post.bundle_index
                passages = []
            else:
                backend = engine.backends[bundle.backend]
                # drop empty-slot sentinels (id=-1, the backend contract's
                # "no lexical match" marker) before resolving payloads — a
                # sentinel would otherwise wrap to the last passage
                real_ids = ids[ids >= 0] if len(ids) else ids
                passages = [p.text for p in backend.get_passages(real_ids)]
        final_bundle.append(bundle_idx)
        passages_all.append(passages)
        confidences.append(confidence)
        prompts.append(build_prompt(routed.queries[i], passages))
        embedded.append(did_embed)
    return AdmittedBatch(
        retrieved=retrieved,
        final_bundle=final_bundle,
        passages=passages_all,
        confidences=confidences,
        prompts=prompts,
        embedded=embedded,
    )


# --------------------------------------------------------------------------- #
# Stage 4: decode (pure) — generation, billing, latency, quality               #
# --------------------------------------------------------------------------- #
@_batch_span
def decode(engine: "RAGEngine", admitted: AdmittedBatch) -> DecodedBatch:
    """Generate per query under its final bundle; bill tokens and sample the
    latency model. Pure given the artifact (generator/latency memo caches
    are idempotent)."""
    routed = admitted.routed
    executions: list[Execution] = []
    exec_cache: dict[tuple[int, int], Execution] = {}
    for i in range(routed.n):
        qid = routed.qid0 + i
        query = routed.queries[i]
        reference = routed.references[i]
        bundle = engine.catalog[admitted.final_bundle[i]]
        answer = engine.generator.generate(
            query, admitted.passages[i], bundle.generation, query_id=qid
        )
        embedded_texts = [query] if admitted.embedded[i] else []
        bill = bill_query(admitted.prompts[i], answer, embedded_texts)
        backend = engine.backends.get(bundle.backend)
        latency_ms = engine.latency_model.sample_ms(
            query_id=qid,
            embed_tokens=bill.embedding_tokens,
            retrieval_k=bundle.top_k,
            prompt_tokens=bill.prompt_tokens,
            completion_tokens=bill.completion_tokens,
            retrieval_latency_scale=(
                backend.cost.latency_scale
                if backend is not None and not bundle.skip_retrieval
                else 1.0
            ),
        )
        quality = (
            lexical_overlap(answer, reference) if reference is not None else float("nan")
        )
        ex = Execution(
            final_bundle_idx=admitted.final_bundle[i],
            passages=admitted.passages[i],
            confidence=admitted.confidences[i],
            answer=answer,
            prompt=admitted.prompts[i],
            bill=bill,
            latency_ms=latency_ms,
            quality=quality,
            degraded=i in admitted.retrieved.fallback_bundle,
            fallback_depth=admitted.retrieved.fallback_depth.get(i, 0),
        )
        executions.append(ex)
        exec_cache[(i, routed.guarded[i])] = ex
    return DecodedBatch(
        admitted=admitted,
        executions=executions,
        exec_cache=exec_cache,
        search_calls=admitted.retrieved.search_calls,
        search_calls_by_backend=dict(admitted.retrieved.search_calls_by_backend),
        cache_events={k: dict(v) for k, v in admitted.retrieved.cache_events.items()},
        resilience=dataclasses.replace(admitted.retrieved.resilience),
    )


# --------------------------------------------------------------------------- #
# Stage 5: finalize (mutates: telemetry, billing ledger; replay fix-up)        #
# --------------------------------------------------------------------------- #
@_batch_span
def finalize(engine: "RAGEngine", decoded: DecodedBatch) -> "list[EngineResponse]":
    """Exact replay + commit. Must be called serially, in arrival order.

    Telemetry refinement makes query i's priors a function of queries < i,
    so position-accurate routing is inherently sequential. The heavy stages
    aren't: retrieval/generation depend only on (query, bundle), and the
    speculation already executed them in batch. One cheap host pass replays
    the telemetry stream on a clone, re-routes each position with its true
    priors (microseconds via the numpy mirror), and re-executes only the
    mispredictions — typically none; under a deep pipeline, whatever the
    staleness of the speculative priors required. Then billing, realized
    utility, telemetry append, and response assembly.
    """
    from repro.serving.engine import EngineResponse

    routed = decoded.routed
    n = routed.n
    qid0 = routed.qid0
    queries, refs = routed.queries, routed.references
    choices, util_np = routed.choices, routed.utilities
    executions = list(decoded.executions)

    if routed.refinement_on:
        choices = choices.copy()
        sim = engine.telemetry.clone_for_replay()
        for i in range(n):
            lp, cp, rp = engine._priors(sim)
            ci, ui = engine.router.route_batch_np(
                routed.complexity[i : i + 1],
                latency_override=lp,
                cost_override=cp,
                recall_override=rp,
            )
            util_np[i] = ui[0]
            choice = int(ci[0])
            if choice != choices[i]:
                choices[i] = choice
                guarded = engine.guardrails.pre_execution(choice).bundle_index
                ex = decoded.exec_cache.get((i, guarded))
                if ex is None:
                    with TraceAnnotation("repro.replay", qid=qid0 + i):
                        sub = execute_one(engine, qid0 + i, queries[i], choice, refs[i])
                    ex = sub.executions[0]
                    # fold the one-element replay execution's search/cache
                    # activity into the batch totals (its plan is empty for
                    # skip-retrieval bundles, so the merge is a no-op there)
                    _fold_searches(decoded, sub)
                    decoded.replayed += 1
                    decoded.exec_cache[(i, guarded)] = ex
                executions[i] = ex
            sim.log(make_record(engine, qid0 + i, queries[i], executions[i], 0.0, 0.0))

    q_realized = np.asarray(
        [ex.quality if refs[i] is not None else 0.0 for i, ex in enumerate(executions)],
        np.float32,
    )
    lat_arr = np.asarray([ex.latency_ms for ex in executions], np.float32)
    cost_arr = np.asarray([ex.bill.total for ex in executions], np.float32)
    realized = np.asarray(
        realized_utility(
            jnp.asarray(q_realized),
            jnp.asarray(lat_arr),
            jnp.asarray(cost_arr),
            weights=engine.router.config.weights,
            norm=engine.config.realized_norm,
        )
    )

    responses = []
    for i, ex in enumerate(executions):
        qid = qid0 + i
        engine.ledger.add(ex.bill)
        record = make_record(
            engine,
            qid,
            queries[i],
            ex,
            float(util_np[i, choices[i]]),
            float(realized[i]),
            complexity=float(routed.complexity[i]),
        )
        engine.telemetry.log(record)
        responses.append(EngineResponse(answer=ex.answer, record=record, passages=ex.passages))
    return responses


# --------------------------------------------------------------------------- #
# Pipeline executor                                                            #
# --------------------------------------------------------------------------- #
class StageError(RuntimeError):
    """A micro-batch died in the middle stages (retrieve/assemble/decode).

    Typed propagation for worker-thread exceptions: instead of a raw
    backend traceback surfacing from a ``Future`` (or worse, an
    unidentifiable batch silently wedging a drain loop), the pipeline wraps
    the failure with the offending micro-batch's identity — its submission
    index and qid range — and chains the original exception as
    ``__cause__``. Fault-family errors never get here on a catalog with a
    direct bundle (the retrieve stage degrades them); StageError means a
    bug, not weather.
    """

    def __init__(self, batch_index: int, qid0: int, n: int, cause: BaseException):
        super().__init__(
            f"pipeline micro-batch {batch_index} (qids {qid0}..{qid0 + n - 1}) "
            f"failed in middle stages: {cause!r}"
        )
        self.batch_index = batch_index
        self.qid0 = qid0
        self.n = n


class StagePipeline:
    """N-deep micro-batch executor over the five stages.

    ``depth`` micro-batches may be in flight between ``route`` and
    ``finalize`` at once; the side-effect-free middle stages
    (retrieve → assemble → decode) drain on ``workers`` threads while the
    caller's thread stays free for token decode. ``route`` runs on the
    submitting thread and ``finalize`` on the polling thread, in strict
    submission order — the recombination barrier that keeps records
    bit-identical to the sequential loop at every setting.

    ``depth=1`` is the fully synchronous path: no worker threads are
    created, ``submit`` runs the middle stages inline, and ``poll`` returns
    the finalized batch immediately (the old ``--no-overlap`` behavior).

    ``executor`` selects where the middle stages run at depth > 1:

    * ``"thread"`` (default) — the in-process worker pool above. Cheap to
      start, overlaps stages with decode, but every stage fights the GIL.
    * ``"process"`` — a :class:`~repro.serving.procpool.
      ProcessStageExecutor`: spawn-context workers that each rebuild the
      engine once from ``engine_factory`` (or share a caller-provided
      ``process_executor``) and drain pickled :class:`RoutedBatch`
      payloads GIL-free. ``route``/``finalize`` stay on the parent — the
      same recombination barrier — so drained records remain bit-identical
      to the sequential loop. Payloads and the factory are audited with
      :func:`~repro.serving.procpool.ensure_picklable` (typed
      ``SpawnSafetyError``, never an opaque pool crash).
    """

    EXECUTORS = ("thread", "process")

    def __init__(
        self,
        engine: "RAGEngine",
        *,
        depth: int = 2,
        workers: int = 1,
        worker_timeout_s: float = 60.0,
        clock=time.monotonic,
        executor: str = "thread",
        engine_factory=None,
        process_executor=None,
    ):
        if executor not in self.EXECUTORS:
            raise ValueError(
                f"unknown executor {executor!r}; expected one of {self.EXECUTORS}"
            )
        self.engine = engine
        self.depth = max(1, int(depth))
        self.workers = max(1, int(workers)) if self.depth > 1 else 0
        self.executor = executor
        self._proc = None
        self._owns_proc = False
        self._pool = None
        if executor == "process" and self.depth > 1:
            if process_executor is not None:
                self._proc = process_executor
            else:
                if engine_factory is None:
                    raise ValueError(
                        "executor='process' needs an engine_factory (a picklable "
                        "zero-arg engine builder, e.g. an EngineSpec) or a "
                        "shared process_executor"
                    )
                from repro.serving.procpool import ProcessStageExecutor

                self._proc = ProcessStageExecutor(
                    engine_factory, max_workers=self.workers
                )
                self._owns_proc = True
        elif self.workers:
            self._pool = ThreadPoolExecutor(max_workers=self.workers)
        # entries carry (tag, work, (batch_index, qid0, n)): the meta lets
        # poll() wrap a raw worker-process exception in a typed StageError
        # without round-tripping the error through a custom pickle path
        self._inflight: deque[tuple[object, Future | DecodedBatch, tuple[int, int, int]]] = deque()
        # deterministic per-stage counters (the CI gate's burst-serial cell)
        self.stage_batches = 0
        self.counts = StageCounts()
        # per-micro-batch worker liveness: each worker beats at batch start
        # and end, so a worker stuck *inside* a batch for > worker_timeout_s
        # shows up in stalled_workers() (training/fault_tolerance reuse)
        self.heartbeats = HeartbeatMonitor([], timeout_s=worker_timeout_s, clock=clock)
        self._busy: dict[str, int] = {}  # worker id → batch index in hand

    def _middle(self, routed: RoutedBatch, batch_index: int) -> DecodedBatch:
        wid = f"worker-{threading.get_ident()}"
        self.heartbeats.beat(wid)
        self._busy[wid] = batch_index
        try:
            return decode(self.engine, assemble(self.engine, retrieve(self.engine, routed)))
        except BaseException as err:
            raise StageError(batch_index, routed.qid0, routed.n, err) from err
        finally:
            self._busy.pop(wid, None)
            self.heartbeats.beat(wid)

    def stalled_workers(self) -> list[str]:
        """Workers holding a micro-batch whose last beat is older than
        ``worker_timeout_s`` — the wedged-shard signal the streaming summary
        surfaces. Idle workers never report (no batch in hand, no deadline)."""
        dead = set(self.heartbeats.dead_workers())
        return sorted(w for w in list(self._busy) if w in dead)

    @property
    def retrieve_calls(self) -> int:
        """search_batch calls of the finalized batches, replays included."""
        return self.counts.search_calls

    @property
    def retrieve_calls_by_backend(self) -> dict[str, int]:
        return self.counts.search_calls_by_backend

    @property
    def cache_events(self) -> dict[str, dict[str, int]]:
        return self.counts.cache_events

    @property
    def resilience(self) -> ResilienceEvents:
        return self.counts.resilience

    @property
    def in_flight(self) -> int:
        """Micro-batches currently between ``route`` and ``finalize``."""
        return len(self._inflight)

    def can_submit(self) -> bool:
        """Whether another micro-batch fits under the configured depth."""
        return len(self._inflight) < self.depth

    def submit(
        self,
        queries: Sequence[str],
        references: Sequence[str | None],
        tag: object = None,
    ) -> None:
        """Route a micro-batch (serially, on this thread) and hand its middle
        stages to the worker pool. ``tag`` is returned with the finalized
        responses by :meth:`poll` (e.g. the arrival events for admission)."""
        if not self.can_submit():
            raise RuntimeError(
                f"pipeline full: {len(self._inflight)} micro-batches in flight "
                f"(depth {self.depth}); poll() before submitting more"
            )
        routed = route(self.engine, queries, references)
        batch_index = self.stage_batches
        self.stage_batches += 1
        work: Future | DecodedBatch
        if self._proc is not None:
            # process path: the worker cannot beat a parent-side heartbeat,
            # so the batch itself is the liveness unit — beat at dispatch,
            # clear on the future's completion callback
            wid = f"proc-{batch_index}"
            self.heartbeats.beat(wid)
            self._busy[wid] = batch_index
            work = self._proc.submit(routed)

            def _clear(_fut, wid=wid):
                self._busy.pop(wid, None)
                self.heartbeats.beat(wid)

            work.add_done_callback(_clear)
        elif self._pool is not None:
            work = self._pool.submit(self._middle, routed, batch_index)
        else:
            work = self._middle(routed, batch_index)
        self._inflight.append((tag, work, (batch_index, routed.qid0, routed.n)))

    def poll(self) -> "tuple[object, list[EngineResponse]] | None":
        """Finalize the oldest micro-batch if its middle stages are done.

        Strict submission-order recombination: only the head of the queue
        may finalize, so telemetry/billing commits happen in arrival order
        no matter how the worker threads interleave."""
        if not self._inflight:
            return None
        tag, work, meta = self._inflight[0]
        if isinstance(work, Future):
            if not work.done():
                return None
            # a worker exception re-raises here typed: the thread path's
            # _middle wrapper already attached StageError (batch index +
            # qid range + cause); a process worker raises raw (StageError's
            # custom __init__ doesn't survive exception pickling), so wrap
            # it here from the head entry's meta. Either way the head stays
            # queued, so the failure is re-observable, never silently
            # dropped.
            try:
                result = work.result()
            except StageError:
                raise
            except BaseException as err:
                batch_index, qid0, n = meta
                raise StageError(batch_index, qid0, n, err) from err
            if self._proc is not None:
                pid, decoded = result
                self._proc.note_batch(pid)
            else:
                decoded = result
        else:
            decoded = work
        self._inflight.popleft()
        responses = finalize(self.engine, decoded)
        self.counts.add(decoded)
        return tag, responses

    def wait_head(self, timeout: float) -> None:
        """Block until the oldest in-flight micro-batch finishes its middle
        stages (or ``timeout`` elapses). No-op when nothing is pending."""
        if self._inflight and isinstance(self._inflight[0][1], Future):
            futures_wait([self._inflight[0][1]], timeout=timeout)

    def process_stats(self) -> dict | None:
        """Worker counters from the process executor (None on thread/serial
        paths): distinct workers seen + sorted batches-per-worker profile."""
        return self._proc.stats() if self._proc is not None else None

    def shutdown(self) -> None:
        """Stop the worker pool (no-op on the depth-1 serial path). An
        owned process executor is shut down too; a shared one is left
        running for its other pipelines."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
        if self._proc is not None and self._owns_proc:
            self._proc.shutdown()
