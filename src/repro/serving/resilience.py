"""Resilient retrieval: timeouts, seeded retries, circuit breakers, ladders.

The serving counterpart of :mod:`repro.retrieval.faults`: given a backend
that *can* fail (injected chaos today, the ROADMAP's ``RemoteBackend``
tomorrow), this module decides what the serving path does about it. Three
mechanisms compose, from innermost to outermost:

* **Per-call timeouts** — a batched search that exceeds ``timeout_ms`` is
  abandoned (the call keeps running on a scavenger thread; its result is
  discarded) and counted as a failed attempt. With ``timeout_ms=None`` the
  call runs inline on the caller's thread — the zero-overhead parity path.
* **Bounded retries with seeded backoff** — up to ``max_retries``
  re-attempts, separated by exponential backoff with deterministic jitter
  (:func:`backoff_delays_ms`): given a fixed seed the whole delay sequence
  is reproducible, so chaos tests can assert on it.
* **A per-backend circuit breaker** — :class:`CircuitBreaker`, the classic
  closed/open/half-open machine with an injectable monotonic clock.
  ``failure_threshold`` consecutive failed attempts open it; while open,
  calls fail fast (no inner call, no retry burn); after ``cooldown_s`` it
  admits exactly ``half_open_probes`` probe calls — one success closes it,
  one failure re-opens it.

When every mechanism is exhausted, :class:`ResilientBackend` raises
:class:`BackendUnavailableError` and the serving ``retrieve`` stage walks
the **degradation ladder** (:func:`degradation_ladder`): bundles from the
engine's own catalog ordered cheaper-backend → shallower-k → the
retrieval-free direct bundle, so every query still gets an answer — tagged
``degraded`` in its :class:`~repro.core.telemetry.QueryRecord` and counted
in the typed :class:`ResilienceEvents` that flow through
``StagePipeline`` into ``StreamResult.summary()["resilience"]``.

Parity contract: wrapping healthy backends changes nothing. A zero-fault
run through ``ResilientBackend`` produces byte-identical CSVs and counters
(the search result passes through untouched; events stay zero) — pinned by
the resilience parity tests.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.core.bundles import BundleCatalog
from repro.retrieval.backend import BackendCost, RetrievalBackend, is_nested
from repro.retrieval.chunking import Passage
from repro.retrieval.faults import RetrievalFault, TransientBackendError


class BackendUnavailableError(RetrievalFault):
    """Raised when a backend's retry budget is exhausted or its breaker is
    open. Carries the call's :class:`ResilienceEvents` so the retrieve
    stage can merge counters even for failed calls."""

    def __init__(self, message: str, *, events: "ResilienceEvents | None" = None):
        super().__init__(message)
        self.events = events if events is not None else ResilienceEvents()


@dataclasses.dataclass
class ResilienceEvents:
    """Typed per-call/per-batch resilience counters.

    One accumulation currency from backend wrapper to stream summary:
    ``ResilientBackend`` emits a delta per search call, the retrieve stage
    folds deltas (plus its own ladder outcomes) into the artifact, the
    :class:`~repro.serving.stages.StagePipeline` accumulates across
    micro-batches, and ``StreamResult.summary()["resilience"]`` surfaces
    the totals.
    """

    retries: int = 0  # re-attempts beyond each call's first
    timeouts: int = 0  # attempts abandoned at timeout_ms
    failures: int = 0  # attempts that raised a transient fault
    short_circuits: int = 0  # calls refused by an open breaker
    breaker_opens: int = 0  # closed/half-open → open transitions
    fallbacks: int = 0  # ladder steps attempted (incl. unsuccessful)
    degraded: int = 0  # queries answered off-plan via the ladder
    fallback_depth_total: int = 0  # sum of per-query ladder depths

    def add(self, other: "ResilienceEvents") -> "ResilienceEvents":
        """Accumulate ``other`` into self (in place); returns self."""
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))
        return self

    def as_dict(self) -> dict[str, int]:
        """Plain-dict view for JSON artifacts and run summaries."""
        return dataclasses.asdict(self)

    @property
    def any(self) -> bool:
        """True if any counter is nonzero (the not-a-clean-run check)."""
        return any(getattr(self, f.name) for f in dataclasses.fields(self))


def backoff_delays_ms(
    n: int,
    *,
    base_ms: float = 1.0,
    multiplier: float = 2.0,
    max_ms: float = 50.0,
    jitter: float = 0.5,
    seed: int = 0,
) -> list[float]:
    """The first ``n`` retry delays: capped exponential with seeded jitter.

    Delay ``i`` is ``min(base·multiplier^i, max) · (1 − jitter·u_i)`` with
    ``u_i ~ U[0,1)`` drawn from ``default_rng(seed)`` — deterministic for a
    fixed seed (the property the hypothesis suite pins), decorrelated
    across calls when the caller varies the seed per call.
    """
    if n <= 0:
        return []
    rng = np.random.default_rng(seed)
    us = rng.random(n)
    out = []
    for i in range(n):
        d = min(base_ms * multiplier**i, max_ms)
        out.append(float(d * (1.0 - jitter * us[i])))
    return out


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded-retry knobs: attempt count and the backoff shape."""

    max_retries: int = 2  # re-attempts; total attempts = 1 + max_retries
    backoff_base_ms: float = 1.0
    backoff_multiplier: float = 2.0
    backoff_max_ms: float = 50.0
    jitter: float = 0.5  # fraction of each delay randomized away
    seed: int = 0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if not (0.0 <= self.jitter <= 1.0):
            raise ValueError(f"jitter must be in [0, 1], got {self.jitter}")

    def delays_ms(self, call_index: int) -> list[float]:
        """This call's full backoff sequence (seeded per call index)."""
        return backoff_delays_ms(
            self.max_retries,
            base_ms=self.backoff_base_ms,
            multiplier=self.backoff_multiplier,
            max_ms=self.backoff_max_ms,
            jitter=self.jitter,
            seed=self.seed + call_index,
        )


@dataclasses.dataclass(frozen=True)
class BreakerConfig:
    """Circuit-breaker thresholds (per backend)."""

    failure_threshold: int = 5  # consecutive failed attempts to open
    cooldown_s: float = 30.0  # open → half-open delay
    half_open_probes: int = 1  # concurrent probes admitted half-open

    def __post_init__(self):
        if self.failure_threshold < 1:
            raise ValueError(f"failure_threshold must be >= 1, got {self.failure_threshold}")
        if self.half_open_probes < 1:
            raise ValueError(f"half_open_probes must be >= 1, got {self.half_open_probes}")


class CircuitBreaker:
    """Closed/open/half-open breaker with an injectable monotonic clock.

    Thread-safe; all transitions happen under one lock. ``allow()`` is the
    admission question ("may I attempt a call now?"); callers report the
    attempt's outcome via ``record_success`` / ``record_failure``. The
    clock is injectable so the state machine is testable without sleeping
    — the hypothesis suite drives it with a virtual clock.
    """

    def __init__(self, config: BreakerConfig = BreakerConfig(), *, clock=time.monotonic):
        self.config = config
        self.clock = clock
        self._lock = threading.Lock()
        self._state = "closed"
        self._consecutive_failures = 0
        self._opened_at = 0.0
        self._probes_inflight = 0
        self.opens = 0  # cumulative closed/half-open → open transitions

    @property
    def state(self) -> str:
        """Current state, refreshing open → half-open on cooldown expiry."""
        with self._lock:
            self._refresh_locked()
            return self._state

    def _refresh_locked(self) -> None:
        if (
            self._state == "open"
            and self.clock() - self._opened_at >= self.config.cooldown_s
        ):
            self._state = "half_open"
            self._probes_inflight = 0

    def _open_locked(self) -> None:
        self._state = "open"
        self._opened_at = self.clock()
        self._consecutive_failures = 0
        self._probes_inflight = 0
        self.opens += 1

    def allow(self) -> bool:
        """Whether an attempt may proceed now (claims a probe if half-open)."""
        with self._lock:
            self._refresh_locked()
            if self._state == "closed":
                return True
            if self._state == "open":
                return False
            if self._probes_inflight >= self.config.half_open_probes:
                return False
            self._probes_inflight += 1
            return True

    def record_success(self) -> None:
        """An allowed attempt succeeded: close (and reset) from any state."""
        with self._lock:
            self._state = "closed"
            self._consecutive_failures = 0
            self._probes_inflight = 0

    def record_failure(self) -> bool:
        """An allowed attempt failed. Returns True if this opened the breaker."""
        with self._lock:
            self._refresh_locked()
            if self._state == "half_open":
                # a failed probe re-opens immediately (fresh cooldown)
                self._open_locked()
                return True
            self._consecutive_failures += 1
            if self._state == "closed" and (
                self._consecutive_failures >= self.config.failure_threshold
            ):
                self._open_locked()
                return True
            return False


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Everything :class:`ResilientBackend` needs: timeout, retry, breaker."""

    timeout_ms: float | None = None  # None = inline call, no timeout thread
    deadline_ms: float | None = None  # total budget per search incl. retries
    retry: RetryPolicy = RetryPolicy()
    breaker: BreakerConfig = BreakerConfig()


# The resilience settings paired with faults.CANONICAL_FAULT_PROFILE for the
# gate cell: timeout comfortably above healthy-call latency but far below the
# canonical stall; a small retry budget; a breaker whose cooldown exceeds any
# bench/test run so "opens" is a deterministic one-way transition there.
CANONICAL_RESILIENCE = ResilienceConfig(
    timeout_ms=250.0,
    retry=RetryPolicy(max_retries=2, backoff_base_ms=1.0, backoff_max_ms=8.0, seed=11),
    breaker=BreakerConfig(failure_threshold=3, cooldown_s=120.0, half_open_probes=1),
)


class ResilientBackend:
    """Timeout + retry + breaker decorator over any retrieval backend.

    Drop-in for the :class:`~repro.retrieval.backend.RetrievalBackend`
    protocol (name/cost/vec-requirement/size/passages delegate). The
    serving ``retrieve`` stage prefers :meth:`search_batch_resilient`,
    which also returns the call's :class:`ResilienceEvents` delta and any
    inner cache delta; plain ``search_batch`` drops the telemetry.

    ``sleep`` (backoff waits) and ``clock`` (deadline + breaker time) are
    injectable for deterministic tests. Timeout execution runs the inner
    call on a small scavenger pool; an abandoned (timed-out) call finishes
    there harmlessly — its result is discarded, and the inner backends are
    pure, so the duplicate work is waste, never corruption.
    """

    def __init__(
        self,
        inner: RetrievalBackend,
        config: ResilienceConfig = ResilienceConfig(),
        *,
        clock=time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.inner = inner
        self.config = config
        self.breaker = CircuitBreaker(config.breaker, clock=clock)
        self._clock = clock
        self._sleep = sleep
        self._lock = threading.Lock()
        self._calls = 0  # per-call seed offset for backoff jitter
        self._pool: ThreadPoolExecutor | None = None

    # -- protocol surface (delegation) --------------------------------------
    @property
    def name(self) -> str:
        """The inner backend's routing name — resilience wrapping is invisible."""
        return self.inner.name

    @property
    def cost(self) -> BackendCost:
        """The inner backend's static cost descriptor, unchanged."""
        return self.inner.cost

    @property
    def requires_query_vecs(self) -> bool:
        """Whether the inner backend consumes embedded query vectors."""
        return self.inner.requires_query_vecs

    @property
    def nested_topk(self) -> bool:
        """The inner backend's: a row it answers is the inner backend's row."""
        return is_nested(self.inner)

    @property
    def size(self) -> int:
        """Corpus passages indexed by the inner backend."""
        return self.inner.size

    def get_passages(self, ids: Sequence[int]) -> list[Passage]:
        """Fetch passage payloads from the inner backend (no retry wrapper:
        payload fetch is a local array lookup, not a remote call)."""
        return self.inner.get_passages(ids)

    def __bool__(self) -> bool:
        """Always truthy regardless of any container-like inner backend."""
        return True

    # -- core ----------------------------------------------------------------
    def _attempt(self, queries, query_vecs, k):
        """One inner attempt, through the timeout harness when configured.

        Returns ``(scores, ids, cache_delta | None)`` — the cache delta when
        the inner backend is cache-wrapped (``search_batch_stats``), so the
        cache observability channel survives resilience wrapping.
        """
        stats_fn = getattr(self.inner, "search_batch_stats", None)

        def call():
            if stats_fn is not None:
                return stats_fn(queries, query_vecs, k)
            scores, ids = self.inner.search_batch(queries, query_vecs, k)
            return scores, ids, None

        if self.config.timeout_ms is None:
            return call()
        if self._pool is None:
            with self._lock:
                if self._pool is None:
                    # small scavenger pool: enough headroom that a few
                    # abandoned stalls can't wedge subsequent attempts
                    self._pool = ThreadPoolExecutor(
                        max_workers=8, thread_name_prefix=f"resilient-{self.name}"
                    )
        fut = self._pool.submit(call)
        try:
            return fut.result(timeout=self.config.timeout_ms / 1000.0)
        except FuturesTimeout:
            fut.cancel()  # best effort; a running call finishes discarded
            raise

    def search_batch_resilient(
        self,
        queries: Sequence[str] | None,
        query_vecs,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray, ResilienceEvents, dict]:
        """Batched search under timeout/retry/breaker discipline.

        Returns ``(scores, ids, events, cache_events)`` on success; raises
        :class:`BackendUnavailableError` (with the events attached) when the
        breaker refuses the call or the retry budget runs dry. Results are
        bit-identical to the inner backend's — resilience only decides
        *whether/when* the inner call runs, never touches its rows.
        """
        ev = ResilienceEvents()
        cache_events: dict[str, dict[str, int]] = {}
        with self._lock:
            call_idx = self._calls
            self._calls += 1
        delays = self.config.retry.delays_ms(call_idx)
        attempts = 1 + self.config.retry.max_retries
        t_start = self._clock()
        last_err: Exception | None = None
        for attempt in range(attempts):
            if not self.breaker.allow():
                ev.short_circuits += 1
                raise BackendUnavailableError(
                    f"circuit breaker open for backend {self.name!r}", events=ev
                ) from last_err
            try:
                out = self._attempt(queries, query_vecs, k)
            except FuturesTimeout as err:
                ev.timeouts += 1
                if self.breaker.record_failure():
                    ev.breaker_opens += 1
                last_err = err
            except TransientBackendError as err:
                ev.failures += 1
                if self.breaker.record_failure():
                    ev.breaker_opens += 1
                last_err = err
            else:
                self.breaker.record_success()
                scores, ids, delta = out
                if delta is not None:
                    tot = cache_events.setdefault(self.name, {})
                    for key, v in delta.as_dict().items():
                        tot[key] = tot.get(key, 0) + v
                return (
                    np.asarray(scores, np.float32),
                    np.asarray(ids, np.int32),
                    ev,
                    cache_events,
                )
            if attempt == attempts - 1:
                break
            if (
                self.config.deadline_ms is not None
                and (self._clock() - t_start) * 1000.0 >= self.config.deadline_ms
            ):
                break  # deadline-aware: don't start attempts we can't afford
            ev.retries += 1
            delay = delays[attempt] if attempt < len(delays) else 0.0
            if delay > 0:
                self._sleep(delay / 1000.0)
        raise BackendUnavailableError(
            f"backend {self.name!r} unavailable after {attempts} attempts "
            f"({ev.failures} failures, {ev.timeouts} timeouts)",
            events=ev,
        ) from last_err

    def search_batch(
        self,
        queries: Sequence[str] | None,
        query_vecs,
        k: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Protocol-shaped search: resilient call with telemetry dropped."""
        scores, ids, _ev, _cache = self.search_batch_resilient(queries, query_vecs, k)
        return scores, ids

    def shutdown(self) -> None:
        """Stop the timeout scavenger pool (idempotent; tests/CLI teardown)."""
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)


def wrap_resilient(
    backends: Mapping[str, RetrievalBackend],
    config: ResilienceConfig = ResilienceConfig(),
    *,
    clock=time.monotonic,
    sleep: Callable[[float], None] = time.sleep,
) -> dict[str, RetrievalBackend]:
    """Wrap every backend of a backend map in :class:`ResilientBackend`
    (outermost layer — above cache/shard/fault decorators), sharing one
    config. Already-resilient backends are left as-is."""
    return {
        name: b
        if isinstance(b, ResilientBackend)
        else ResilientBackend(b, config, clock=clock, sleep=sleep)
        for name, b in backends.items()
    }


def degradation_ladder(catalog: BundleCatalog, bundle_idx: int) -> list[int]:
    """Fallback bundle indices for a failed retrieval, best first.

    Derived entirely from the engine's own catalog — the ladder is not a
    config surface. Ordering implements cheaper-backend → shallower-k →
    direct:

    1. bundles on a *different* backend whose effective latency prior is no
       worse and whose depth is no deeper (a cheaper/healthier replica of
       roughly the same plan), best effective quality first;
    2. bundles on the *same* backend with strictly shallower ``top_k``
       (smaller ask of a struggling service — and on a wrapped backend each
       rung re-enters the retry/breaker discipline), deepest first;
    3. retrieval-free bundles (always-succeeds direct inference), best
       quality prior first.

    The retrieve stage walks the rungs in order and stops at the first that
    answers; rung 3 cannot fail, so a catalog with a direct bundle (both
    shipped presets) guarantees every query an answer.
    """
    b = catalog[bundle_idx]
    cheaper: list[int] = []
    shallower: list[int] = []
    direct: list[int] = []
    for i, cand in enumerate(catalog):
        if i == bundle_idx:
            continue
        if cand.skip_retrieval:
            direct.append(i)
        elif (
            cand.backend != b.backend
            and cand.effective_latency_prior_ms <= b.effective_latency_prior_ms
            and cand.top_k <= b.top_k
        ):
            cheaper.append(i)
        elif cand.backend == b.backend and cand.top_k < b.top_k:
            shallower.append(i)
    cheaper.sort(key=lambda i: -catalog[i].effective_quality_prior)
    shallower.sort(key=lambda i: -catalog[i].top_k)
    direct.sort(key=lambda i: -catalog[i].quality_prior)
    return cheaper + shallower + direct
