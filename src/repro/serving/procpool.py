"""Process-level stage execution: spawn-safe worker pools for the pipeline.

Every parallel path in the repo used to run under one Python GIL, so the
``StagePipeline``'s worker threads bought overlap with *decode* but never
true stage parallelism — jit dispatch, numpy reshuffles, and prompt
assembly all serialize on the interpreter lock. This module moves the pure
middle stages (retrieve → assemble → decode) **out of process**:

* :class:`ProcessStageExecutor` owns a spawn-context
  ``ProcessPoolExecutor`` whose workers each rebuild the engine **once**
  (backend stack, jit closures, generator caches) from a picklable
  ``engine_factory``, then drain routed micro-batches sent over as pickled
  :class:`~repro.serving.stages.RoutedBatch` payloads.
* :class:`EngineSpec` is the canonical picklable factory: a frozen
  description (policy, catalog, epsilon, embed dim, backend-stack config)
  that ``build()``s the same engine on any process.
* :func:`ensure_picklable` is the fail-fast audit: anything that cannot
  cross the process boundary (a live ``FaultyBackend`` rng, a lambda, a
  thread lock) raises a typed :class:`SpawnSafetyError` at submission
  time, not as an opaque pool crash later.

Exactness is preserved because the middle stages are pure functions of
(artifact, engine construction): a worker engine built from the same spec
computes bit-identical retrievals, prompts, bills, and latencies (all
seeded per ``query_id``), and ``route``/``finalize`` — the only stages
that touch shared mutable state — never leave the parent process. The
finalize-stage replay then repairs any speculative staleness exactly as it
does for threads, so drained runs stay byte-identical to ``answer_batch``
at every (executor, depth, workers) setting.

Spawn (never fork) is mandatory: the parent holds jax runtime threads and
jit caches that do not survive a fork. A spawned worker re-imports the
code, pays one engine build (~1 s on the paper corpus), and amortizes it
over every micro-batch it drains.

On an accelerator the parent holds the chip, and a worker that builds jax
state could not reach it: :class:`ProcessStageExecutor` then refuses with
:class:`~repro.runtime.AcceleratorHeldError` before spawning anything.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from concurrent.futures import Future, ProcessPoolExecutor
from multiprocessing import get_context
from typing import TYPE_CHECKING, Callable

from repro.runtime import refuse_children_on_accelerator

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.retrieval.stack import BackendStackConfig
    from repro.serving.engine import RAGEngine
    from repro.serving.stages import DecodedBatch, RoutedBatch


class SpawnSafetyError(TypeError):
    """A factory or stage payload cannot cross a process boundary.

    Raised *before* anything is submitted to the pool, naming the offending
    object, so a non-picklable component (an in-process ``FaultyBackend``
    holding a live rng/lock, a lambda factory, a backend with open pipes)
    fails fast at the call site instead of surfacing as an unexplained
    ``BrokenProcessPool`` from a worker.
    """


def ensure_picklable(obj: object, what: str) -> bytes:
    """Pickle ``obj`` or raise a typed :class:`SpawnSafetyError`.

    Returns the pickle bytes so callers pay serialization exactly once —
    the audit *is* the encoding that ships to the worker.
    """
    try:
        return pickle.dumps(obj)
    except Exception as err:
        raise SpawnSafetyError(
            f"{what} cannot be sent to a process executor: {err!r}. "
            "Process workers receive pickled payloads and rebuild live "
            "components (engines, backends, rngs) from picklable specs — "
            "pass an EngineSpec / module-level factory instead of an object "
            "holding locks, sockets, or closures."
        ) from err


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Picklable recipe for rebuilding a paper-corpus engine in a worker.

    The process-executor counterpart of ``build_paper_engine``: everything
    that determines engine behavior — routing policy, bundle catalog,
    exploration epsilon, embedding dim, and the declarative backend stack —
    as plain data. ``build()`` (or calling the spec) constructs the engine;
    two processes building the same spec produce engines whose pure middle
    stages are bit-identical.
    """

    policy: str = "router_default"
    catalog: str = "paper"
    epsilon: float = 0.0
    embed_dim: int = 256
    stack: "BackendStackConfig | None" = None

    def build(self) -> "RAGEngine":
        """Construct the engine this spec describes (heavy: index build +
        jit warmup happen here, once per worker)."""
        from repro.core.bundles import make_catalog
        from repro.core.policies import make_policy
        from repro.core.router import RouterConfig
        from repro.serving.engine import build_paper_engine

        router = make_policy(
            self.policy,
            catalog=make_catalog(self.catalog),
            config=RouterConfig(epsilon=self.epsilon),
        )
        return build_paper_engine(router, embed_dim=self.embed_dim, stack=self.stack)

    def __call__(self) -> "RAGEngine":
        return self.build()


# One engine per worker process, built by the pool initializer and reused
# by every micro-batch that worker drains (module global: ProcessPoolExecutor
# initializers have no other channel to per-worker state).
_WORKER_ENGINE = None


def _worker_init(factory_bytes: bytes) -> None:
    """Pool initializer: rebuild the engine once in this worker process."""
    global _WORKER_ENGINE
    factory = pickle.loads(factory_bytes)
    _WORKER_ENGINE = factory()


def _worker_middle(routed_bytes: bytes) -> "tuple[int, DecodedBatch]":
    """Run retrieve → assemble → decode on this worker's engine.

    Returns ``(pid, decoded)`` so the parent can attribute the batch to a
    worker (the CI gate's batches-per-worker counter). Exceptions propagate
    raw — the parent pipeline wraps them in ``StageError`` with the batch's
    identity, which it knows and this process does not need to.
    """
    if _WORKER_ENGINE is None:
        raise RuntimeError(
            "process worker has no engine: the pool initializer did not run "
            "(was the executor constructed with an engine_factory?)"
        )
    from repro.serving.stages import assemble, decode, retrieve

    routed = pickle.loads(routed_bytes)
    engine = _WORKER_ENGINE
    return os.getpid(), decode(engine, assemble(engine, retrieve(engine, routed)))


def _worker_pid() -> int:
    """No-op probe used by :meth:`ProcessStageExecutor.warm`."""
    return os.getpid()


class ProcessStageExecutor:
    """Persistent spawn-context worker pool for the pipeline middle stages.

    Construction validates the factory is picklable (typed
    :class:`SpawnSafetyError` otherwise) but spawns lazily: workers start
    on first submit (or :meth:`warm`), each paying one ``factory()`` engine
    build via the pool initializer. The executor is shareable across
    pipelines — benchmarks pass one instance through several
    ``StreamConfig`` cells so the spawn cost is paid once.
    """

    def __init__(
        self,
        engine_factory: "Callable[[], RAGEngine]",
        *,
        max_workers: int = 1,
        mp_context: str = "spawn",
    ):
        refuse_children_on_accelerator("executor='process'")
        self._factory_bytes = ensure_picklable(engine_factory, "engine factory")
        self.max_workers = max(1, int(max_workers))
        self._pool = ProcessPoolExecutor(
            max_workers=self.max_workers,
            mp_context=get_context(mp_context),
            initializer=_worker_init,
            initargs=(self._factory_bytes,),
        )
        # pid → micro-batches drained there (parent-side, fed by note_batch)
        self.batches_by_pid: dict[int, int] = {}

    def submit(self, routed: "RoutedBatch") -> "Future[tuple[int, DecodedBatch]]":
        """Ship one routed micro-batch to a worker (fail-fast pickling)."""
        payload = ensure_picklable(routed, "stage payload (RoutedBatch)")
        return self._pool.submit(_worker_middle, payload)

    def note_batch(self, pid: int) -> None:
        """Record one drained micro-batch against its worker pid."""
        self.batches_by_pid[pid] = self.batches_by_pid.get(pid, 0) + 1

    def stats(self) -> dict:
        """Deterministic worker counters (the CI gate's process cell):
        distinct workers seen and the sorted batches-per-worker profile."""
        return {
            "n_workers": len(self.batches_by_pid),
            "batches_per_worker": sorted(self.batches_by_pid.values(), reverse=True),
        }

    def warm(self) -> None:
        """Spawn the workers and build their engines now, so the first real
        micro-batch doesn't pay the ~1 s spawn + engine build."""
        futs = [self._pool.submit(_worker_pid) for _ in range(self.max_workers)]
        for f in futs:
            f.result()

    def shutdown(self) -> None:
        """Stop the worker processes (joins them; safe to call twice)."""
        self._pool.shutdown(wait=True)
