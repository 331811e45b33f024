"""CA-RAG serving entry point: route → retrieve → generate over a query file.

    PYTHONPATH=src python -m repro.launch.serve \
        --docs data/documents.txt --questions data/questions.txt \
        --policy router_default --out results/serve.csv

Defaults reproduce the paper benchmark exactly (Appendix D/E artifacts).
"""

from __future__ import annotations

import argparse


def build_engine_from_opts(opts: dict) -> "object":
    """Build the serving engine from a plain-dict option bag.

    Module-level, driven purely by picklable primitives (paths, numbers,
    ``NAME=VAL`` strings), so ``functools.partial(build_engine_from_opts,
    opts)`` is a spawn-safe engine factory: ``--executor process`` workers
    rebuild the exact engine — corpus, backend stack, fault schedules,
    guardrails — the parent serves, which is what keeps worker-computed
    middle stages bit-identical to the parent's replay.

    Raises ``SystemExit`` with a CLI-shaped message on invalid options
    (the parent always validates first, so workers never see these).
    """
    from repro.core.bundles import make_catalog
    from repro.core.guardrails import GuardrailConfig
    from repro.core.policies import make_policy
    from repro.core.router import RouterConfig
    from repro.data.benchmark import corpus_document
    from repro.retrieval import (
        BackendStackConfig,
        DenseIndex,
        FaultProfile,
        HashedNGramEmbedder,
        build_backend_stack,
        line_passages,
        make_backends,
    )
    from repro.serving.engine import EngineConfig, RAGEngine

    catalog = make_catalog(opts["catalog"])
    router = make_policy(
        opts["policy"], catalog=catalog, config=RouterConfig(epsilon=opts["epsilon"])
    )
    if opts["synthetic_docs"] > 0:
        if opts["docs"]:
            raise SystemExit("--synthetic-docs and --docs are mutually exclusive")
        from repro.retrieval import synthetic_dense_index

        embedder = HashedNGramEmbedder(dim=opts["synthetic_dim"])
        index = synthetic_dense_index(
            opts["synthetic_docs"], opts["synthetic_dim"], seed=opts["synthetic_seed"]
        )
        passages = index.passages
        index_tokens = 0  # nothing was embedded: the corpus is fabricated
    else:
        doc = open(opts["docs"]).read() if opts["docs"] else corpus_document()
        embedder = HashedNGramEmbedder(dim=256)
        passages = line_passages(doc)
        index, index_tokens = DenseIndex.build(passages, embedder)
    backends = make_backends(
        index, passages, embedder, names=("dense", *catalog.backends_used())
    )

    fault_profiles: dict[str, FaultProfile] = {}
    for spec in opts["fault_profile"]:
        try:
            name, profile = FaultProfile.parse(spec)
        except ValueError as err:
            raise SystemExit(f"--fault-profile: {err}")
        if name not in backends:
            raise SystemExit(
                f"--fault-profile: unknown backend {name!r} "
                f"(this catalog serves {sorted(backends)})"
            )
        fault_profiles[name] = profile
    remote_backends: dict[str, str] = {}
    for item in opts["remote_backend"]:
        name, sep, addr = item.partition("=")
        if not sep or not name or not addr:
            raise SystemExit(
                f"--remote-backend expects NAME=HOST:PORT, got {item!r}"
            )
        remote_backends[name] = addr
    resilience: object = None
    if (
        opts["retrieve_timeout_ms"] is not None
        or opts["max_retries"] is not None
        or fault_profiles
    ):
        from repro.serving.resilience import ResilienceConfig, RetryPolicy

        resilience = ResilienceConfig(
            timeout_ms=opts["retrieve_timeout_ms"],
            retry=RetryPolicy(
                max_retries=opts["max_retries"] if opts["max_retries"] is not None else 2
            ),
        )
    # One declarative recipe for the whole decorator stack — ordering
    # (remote → shard → faults → cache → resilience) lives in
    # build_backend_stack, not here.
    try:
        stack = BackendStackConfig(
            shards=opts["shards"],
            shard_execution=opts["shard_execution"],
            shard_backends=tuple(
                n.strip() for n in opts["shard_backends"].split(",") if n.strip()
            ),
            remote_backends=remote_backends,
            cache_size=opts["cache_size"],
            fault_profiles=fault_profiles,
            resilience=resilience,
        )
    except ValueError as err:
        raise SystemExit(f"invalid backend stack: {err}")
    backends = build_backend_stack(backends, stack, index=index)

    per_backend_conf: dict[str, float] = {}
    for item in opts["min_confidence_backend"]:
        name, sep, val = item.partition("=")
        try:
            threshold = float(val)
        except ValueError:
            threshold = None
        if not sep or not name or threshold is None:
            raise SystemExit(
                f"--min-confidence-backend expects NAME=VAL, got {item!r}"
            )
        if name not in backends:
            # a typo here would silently fall back to the global threshold —
            # exactly the guardrail hole the flag exists to close
            raise SystemExit(
                f"--min-confidence-backend: unknown backend {name!r} "
                f"(this catalog serves {sorted(backends)})"
            )
        per_backend_conf[name] = threshold

    return RAGEngine(
        router,
        index,
        embedder,
        catalog=router.catalog,
        backends=backends,
        config=EngineConfig(
            guardrails=GuardrailConfig(
                min_retrieval_confidence=opts["min_confidence"],
                max_cost_tokens=opts["max_cost_tokens"],
                min_retrieval_confidence_by_backend=per_backend_conf or None,
            )
        ),
        index_embedding_tokens=index_tokens,
    )


_ENGINE_OPT_KEYS = (
    "docs", "policy", "catalog", "epsilon", "min_confidence",
    "min_confidence_backend", "max_cost_tokens", "cache_size", "shards",
    "shard_backends", "shard_execution", "remote_backend", "synthetic_docs",
    "synthetic_dim", "synthetic_seed", "fault_profile", "retrieve_timeout_ms",
    "max_retries",
)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument parser; ``parse_args([])`` gives its
    defaults (``chip_smoke.py`` builds its engines from these)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", default=None, help="newline-separated passages (default: paper corpus)")
    ap.add_argument("--questions", default=None, help="one query per line (default: paper queries)")
    ap.add_argument("--policy", default="router_default")
    ap.add_argument(
        "--catalog", default="paper", choices=("paper", "extended"),
        help="bundle catalog preset: 'paper' = Table I (dense-only, parity-"
        "pinned); 'extended' adds BM25-light / IVF-medium / hybrid-heavy "
        "bundles routed through the pluggable retrieval backends",
    )
    ap.add_argument("--out", default="results/serve.csv")
    ap.add_argument("--epsilon", type=float, default=0.0)
    ap.add_argument("--min-confidence", type=float, default=0.0)
    ap.add_argument(
        "--min-confidence-backend", action="append", default=[], metavar="NAME=VAL",
        help="per-backend low-confidence threshold override (repeatable), "
        "e.g. --min-confidence-backend bm25=2.5 — confidence units differ "
        "per backend (docs/retrieval.md), so lexical bundles need their own "
        "scale; 0 disables the guardrail for that backend",
    )
    ap.add_argument("--max-cost-tokens", type=int, default=None)
    ap.add_argument(
        "--cache-size", type=int, default=0, metavar="N",
        help="wrap every retrieval backend in an exact query-result LRU of N "
        "entries (0 = no caching); repeated queries are served at memory "
        "speed with bit-identical results",
    )
    ap.add_argument(
        "--shards", type=int, default=1, metavar="S",
        help="partition the dense corpus across S shards (bit-identical to "
        "unsharded). 1 = single index",
    )
    ap.add_argument(
        "--shard-backends", default="dense", metavar="NAMES",
        help="comma-separated backend names --shards partitions (default "
        "'dense'). Adding bm25/ivf shards those too — replicated global "
        "idf/avgdl and centroid stats keep results bit-identical; sparse "
        "methods always shard on the threads path (--shard-execution "
        "governs dense only)",
    )
    ap.add_argument(
        "--shard-execution", default="threads",
        choices=("threads", "process", "device", "auto"),
        help="how sharded search runs: 'threads' fans per-shard searches out "
        "on host threads; 'process' fans out to persistent per-shard worker "
        "processes (GIL-free — the multi-core CPU host path; refused on an "
        "accelerator, whose chip this process holds); 'device' lowers "
        "search + top-k merge onto the jax device mesh as one shard_map "
        "program (requires >= S devices; on CPU hosts set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=S); 'auto' picks "
        "inline threads or process by core count, and never process on an "
        "accelerator. All are bit-identical to "
        "unsharded retrieval (docs/retrieval.md)",
    )
    ap.add_argument(
        "--remote-backend", action="append", default=[], metavar="NAME=HOST:PORT",
        help="serve backend NAME through a remote retrieval service "
        "(repeatable), e.g. --remote-backend dense=127.0.0.1:8631 — the "
        "named backend is replaced by a RemoteBackend RPC client; start the "
        "service with python -m repro.launch.serve_backend. Cache/"
        "resilience layers wrap the remote client unchanged",
    )
    ap.add_argument(
        "--synthetic-docs", type=int, default=0, metavar="N",
        help="replace the corpus with N seeded synthetic documents (random "
        "unit embeddings + placeholder passages) — the retrieval-scaling "
        "configuration: quality is meaningless, systems behaviour "
        "(sharding, caching, latency) is real. Mutually exclusive with "
        "--docs; 0 = use the real corpus",
    )
    ap.add_argument(
        "--synthetic-dim", type=int, default=64, metavar="D",
        help="embedding dimension for --synthetic-docs (default 64; a "
        "million-doc corpus at D=64 is ~256 MB of float32)",
    )
    ap.add_argument(
        "--synthetic-seed", type=int, default=0,
        help="RNG seed for the --synthetic-docs corpus (same seed = "
        "bit-identical corpus)",
    )
    ap.add_argument(
        "--fault-profile", action="append", default=[], metavar="NAME:K=V,...",
        help="inject a seeded fault schedule into backend NAME (repeatable), "
        "e.g. --fault-profile dense:failure_rate=0.3,stall_every=6,"
        "stall_ms=1500,seed=2 — keys are FaultProfile fields; pair with "
        "--retrieve-timeout-ms/--max-retries to exercise the resilience "
        "ladder (docs/resilience.md)",
    )
    ap.add_argument(
        "--retrieve-timeout-ms", type=float, default=None, metavar="MS",
        help="per-search_batch timeout; a timed-out call counts as a failure "
        "and is retried. Enables the ResilientBackend wrapper (with retries, "
        "circuit breaker, and the degradation ladder) even at 0 retries",
    )
    ap.add_argument(
        "--max-retries", type=int, default=None, metavar="N",
        help="bounded seeded-backoff retries per retrieval call (default 2 "
        "when resilience is active); enables the ResilientBackend wrapper",
    )
    ap.add_argument(
        "--request-deadline-ms", type=float, default=None, metavar="MS",
        help="per-request wall-clock deadline from arrival (--stream only); "
        "requests already late at admission get a typed deadline_exceeded "
        "rejection instead of burning decode slots",
    )
    ap.add_argument(
        "--scenario", default=None, metavar="NAME",
        help="run a named workload scenario from the declarative suite "
        "(serving/scenarios.py) instead of the query file: corpus, stream, "
        "engine stack, and SLO targets all come from the seeded spec; "
        "prints the scenario's JSON cell and writes telemetry to --out. "
        "Mutually exclusive with --stream/--docs/--questions",
    )
    ap.add_argument(
        "--scenario-scale", type=float, default=1.0, metavar="X",
        help="scale the scenario's stream lengths and intake caps by X "
        "(--scenario only; the gated counters only hold at 1)",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="serve from a live Poisson arrival queue (retrieval/decode overlap) "
        "instead of one pre-collected batch",
    )
    ap.add_argument("--rate-qps", type=float, default=0.0,
                    help="offered load for --stream; <=0 means all arrive at t=0")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="micro-batches in flight through the stage pipeline "
                    "(--stream only; 1 = fully serial)")
    ap.add_argument("--retrieval-workers", type=int, default=1,
                    help="workers draining the retrieve/assemble/decode "
                    "stages (--stream only; ignored at depth 1)")
    ap.add_argument(
        "--executor", default="thread", choices=("thread", "process"),
        help="where the pipeline's middle stages run (--stream only): "
        "'thread' = in-process worker threads (GIL-bound); 'process' = "
        "spawn-context worker processes that each rebuild this engine once "
        "and drain micro-batches GIL-free (CPU hosts only: refused on an "
        "accelerator, whose chip this process holds). Records are "
        "bit-identical either way (docs/serving.md)",
    )
    ap.add_argument("--tokens-per-s", type=float, default=None,
                    help="pace the slot decoder's step clock (--stream only; "
                    "default: free-running)")
    ap.add_argument("--seed", type=int, default=0, help="arrival-trace seed (--stream)")
    return ap


def _shard_counts(backends) -> str:
    """The sharded dense backend's work counters, found under any wrappers
    (cache, faults, resilience), for the ``served:`` line; empty where the
    dense backend is not sharded."""
    from repro.retrieval import ShardedBackend

    dense = backends.get("dense")
    while dense is not None and not isinstance(dense, ShardedBackend):
        dense = getattr(dense, "inner", None)
    return "" if dense is None else f", dense shards {dense.counters.as_dict()}"


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()

    from repro.runtime import enable_compilation_cache

    enable_compilation_cache()
    if args.scenario is not None:
        import json

        if args.stream or args.docs or args.questions:
            ap.error("--scenario is mutually exclusive with --stream/--docs/--questions")
        from repro.serving.scenarios import SCENARIOS, run_scenario

        spec = SCENARIOS.get(args.scenario)
        if spec is None:
            ap.error(
                f"unknown scenario {args.scenario!r}; "
                f"available: {', '.join(sorted(SCENARIOS))}"
            )
        result = run_scenario(spec, scale=args.scenario_scale)
        print(json.dumps({args.scenario: result.cell}, indent=2))
        # telemetry CSV comes from the scenario's own engine — the records
        # behind the cell's completed/degraded counters
        telemetry = result.engine.telemetry
        telemetry.to_csv(args.out)
        print(f"wrote {len(telemetry.records)} records to {args.out}")
        return

    from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS

    if args.questions:
        with open(args.questions) as f:
            queries = [line.strip() for line in f if line.strip()]
        references = None
    else:
        queries = list(BENCHMARK_QUERIES)
        references = list(REFERENCE_ANSWERS)

    opts = {key: getattr(args, key) for key in _ENGINE_OPT_KEYS}
    engine = build_engine_from_opts(opts)
    catalog = engine.catalog
    if args.stream:
        import functools
        import json
        import math

        from repro.serving.generator import TransformerSlotDecoder
        from repro.serving.streaming import StreamConfig, serve_stream

        depth = args.pipeline_depth
        decoder = TransformerSlotDecoder.tiny(n_slots=8, tokens_per_s=args.tokens_per_s)
        decoder.warmup()  # decode-step compile must not bill to the first batch's TTFT
        result = serve_stream(
            engine,
            queries,
            references,
            rate_qps=args.rate_qps if args.rate_qps > 0 else math.inf,
            seed=args.seed,
            decode_fn=decoder,
            config=StreamConfig(
                overlap=depth > 1,
                pipeline_depth=depth,
                retrieval_workers=args.retrieval_workers,
                executor=args.executor,
                request_deadline_ms=args.request_deadline_ms,
            ),
            # spawn-safe: workers rebuild this exact engine from the same
            # plain-dict options the parent used
            engine_factory=functools.partial(build_engine_from_opts, opts),
        )
        print(json.dumps(result.summary(), indent=2))
        if result.rejections:
            print(f"rejected {len(result.rejections)} requests "
                  f"(first: {result.rejections[0].reason})")
    telemetry = engine.telemetry if args.stream else engine.run(queries, references)
    telemetry.to_csv(args.out)
    print(telemetry.summary_json())
    if not args.stream:
        # what answer_batch served: replays are re-executions whose refined
        # route flipped, each one more full search of the index
        counts = engine.counts
        print(f"served: {counts.routed} routed, {counts.replayed} replayed "
              f"({100 * counts.replayed / max(1, counts.routed):.2f}%), "
              f"searches by backend {counts.search_calls_by_backend}"
              f"{_shard_counts(engine.backends)}")
    if args.catalog != "paper":
        # (backend × depth) routing view: which retrieval method served what
        print(f"routed by backend: {catalog.routed_by_backend(telemetry.strategy_counts())}")
    if args.cache_size > 0:
        from repro.retrieval import cache_stats_view

        print(f"backend cache: {cache_stats_view(engine.backends)}")
    print(f"wrote {len(telemetry.records)} records to {args.out}")


if __name__ == "__main__":
    main()
