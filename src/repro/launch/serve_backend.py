"""Retrieval-as-a-service entry point: serve one backend over a socket.

    PYTHONPATH=src python -m repro.launch.serve_backend \
        --backend dense --port 8631

Pairs with ``python -m repro.launch.serve --remote-backend dense=HOST:PORT``
on the client side: the serving engine's backend map gets a
:class:`~repro.retrieval.remote.RemoteBackend` RPC client in place of the
named backend, and every client-side decorator (cache, faults, resilience)
wraps the network hop unchanged. The service can itself shard — ``--shards``
builds the served backend through the same declarative stack the engine
uses, so a remote dense backend can fan out across shards server-side.

The service and its client each build jax state, so on a TPU host they do
not share one chip: a chip belongs to one process at a time. Run the
service on a host of its own, or keep the backend in the serving process.
"""

from __future__ import annotations

import argparse


def build_served_backend(args: argparse.Namespace):
    """Build the one backend this process serves (corpus + optional shards)."""
    from repro.retrieval import (
        BackendStackConfig,
        DenseIndex,
        HashedNGramEmbedder,
        build_backend_stack,
        line_passages,
        make_backends,
    )

    if args.synthetic_docs > 0:
        if args.docs:
            raise SystemExit("--synthetic-docs and --docs are mutually exclusive")
        from repro.retrieval import synthetic_dense_index

        embedder = HashedNGramEmbedder(dim=args.synthetic_dim)
        index = synthetic_dense_index(
            args.synthetic_docs, args.synthetic_dim, seed=args.synthetic_seed
        )
        passages = index.passages
    else:
        from repro.data.benchmark import corpus_document

        doc = open(args.docs).read() if args.docs else corpus_document()
        embedder = HashedNGramEmbedder(dim=256)
        passages = line_passages(doc)
        index, _ = DenseIndex.build(passages, embedder)

    names = ("dense",) if args.backend == "dense" else ("dense", args.backend)
    backends = make_backends(index, passages, embedder, names=names)
    if args.shards > 1:
        stack = BackendStackConfig(
            shards=args.shards,
            shard_execution=args.shard_execution,
            shard_backends=(args.backend,),
        )
        backends = build_backend_stack(backends, stack, index=index)
    return backends[args.backend]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--backend", default="dense", choices=("dense", "bm25", "ivf", "hybrid"),
        help="which retrieval backend this service exposes (default dense)",
    )
    ap.add_argument("--docs", default=None,
                    help="newline-separated passages (default: paper corpus)")
    ap.add_argument(
        "--synthetic-docs", type=int, default=0, metavar="N",
        help="serve a seeded synthetic corpus of N documents instead of "
        "--docs (systems benchmarking; mutually exclusive with --docs)",
    )
    ap.add_argument("--synthetic-dim", type=int, default=64, metavar="D",
                    help="embedding dimension for --synthetic-docs")
    ap.add_argument("--synthetic-seed", type=int, default=0,
                    help="RNG seed for the --synthetic-docs corpus")
    ap.add_argument(
        "--shards", type=int, default=1, metavar="S",
        help="shard the served backend S ways server-side (bit-identical; "
        "this is where sharding lives when the client uses --remote-backend)",
    )
    ap.add_argument(
        "--shard-execution", default="threads",
        choices=("threads", "process", "device", "auto"),
        help="shard fan-out execution for --shards (see serve --help)",
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8631,
                    help="listening port (0 binds an ephemeral port)")
    ap.add_argument(
        "--format", default=None, choices=("msgpack", "json"),
        help="wire encoding (default: msgpack when importable, else json)",
    )
    args = ap.parse_args()

    from repro.retrieval.remote import BackendServer
    from repro.runtime import enable_compilation_cache

    enable_compilation_cache()
    backend = build_served_backend(args)
    server = BackendServer(backend, host=args.host, port=args.port, fmt=args.format)
    print(
        f"serving backend {backend.name!r} ({backend.size} passages) "
        f"on {server.host}:{server.port} [{server.fmt}] — "
        f"connect with: --remote-backend {args.backend}={server.host}:{server.port}"
    )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
