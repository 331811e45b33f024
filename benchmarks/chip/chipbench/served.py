"""The system under test, assembled from the program's public constructors,
and warmed; what a measured window served.

The engine is the composition ``launch/serve.build_engine_from_opts``
performs, with the serve CLI's defaults for every option the
configuration's ``serve_args`` do not set, over an index the benchmark
hands in: the configuration's corpus, not the CLI's synthetic one.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def serve_options(serve_args: list[str]):
    """The serve CLI's options for ``serve_args`` (its defaults otherwise)."""
    from repro.launch.serve import build_parser

    return build_parser().parse_args(list(serve_args))


def build_engine(index, passages, dim: int, opts):
    """A fresh engine over ``index``: its compiled search programs are the
    index's, so engines built over one index share them."""
    from repro.core.bundles import make_catalog
    from repro.core.guardrails import GuardrailConfig
    from repro.core.policies import make_policy
    from repro.core.router import RouterConfig
    from repro.retrieval import (
        BackendStackConfig,
        HashedNGramEmbedder,
        build_backend_stack,
        make_backends,
    )
    from repro.serving.engine import EngineConfig, RAGEngine

    catalog = make_catalog(opts.catalog)
    router = make_policy(opts.policy, catalog=catalog, config=RouterConfig(epsilon=opts.epsilon))
    embedder = HashedNGramEmbedder(dim=dim)
    backends = make_backends(index, passages, embedder, names=("dense", *catalog.backends_used()))
    stack = BackendStackConfig(
        shards=opts.shards,
        shard_execution=opts.shard_execution,
        shard_backends=tuple(n.strip() for n in opts.shard_backends.split(",") if n.strip()),
        cache_size=opts.cache_size,
    )
    backends = build_backend_stack(backends, stack, index=index)
    return RAGEngine(
        router,
        index,
        embedder,
        catalog=router.catalog,
        backends=backends,
        config=EngineConfig(
            guardrails=GuardrailConfig(
                min_retrieval_confidence=opts.min_confidence,
                max_cost_tokens=opts.max_cost_tokens,
            )
        ),
        index_embedding_tokens=0,
    )


def routed_depths(engine) -> list[int]:
    """Every k the engine's catalog can route a retrieval to."""
    return sorted({b.top_k for b in engine.catalog if not b.skip_retrieval})


def warm_up(engine, sizes, warm_queries) -> None:
    """Compile every shape the window will use: the search program of each
    routable k, and the stages at each batch size in ``sizes``."""
    import jax.numpy as jnp

    dense = engine.backends["dense"]
    for k in routed_depths(engine):
        _, ids = dense.search_batch(["warm-up"], jnp.zeros((1, engine.index.dim), jnp.float32), k)
        np.asarray(ids)
    for n in sizes:
        engine.answer_batch(warm_queries.next_batch(n))


@dataclasses.dataclass
class Window:
    """What one measured window served. An arrivals driver
    (``arrivals/<kind>.py``) returns it; one with more to report may
    return a subclass, which its own metric readers read."""

    seconds: float  # every second of the window, host clock
    queries: list[str]  # in the order they were sent
    responses: list  # EngineResponse per answered query, same order
    attempted: int
    failed: int
