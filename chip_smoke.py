"""Bring-up smoke of the served CA-RAG path on a TPU.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

Phases, all in this one process (a chip belongs to one process at a time):

(a) paper path: the serve CLI's default engine (paper catalog, paper
    corpus) answers the 28 benchmark queries. Routing must match the
    committed paper cell (``results/BENCH_serving.json``) and every
    retrieved passage list must equal a float64 exact-MIPS reference.
(b) real-size retrieval: the same entry point over a seeded synthetic
    corpus of 10⁶ passages × 768 dims (f32, 3.1 GB), served through
    ``answer_batch`` with the served scorer, then the compiled Pallas
    ``mips_topk`` kernel for k ∈ {3, 5, 10}; ids are checked against the
    float64 reference on sampled queries.
(c) streaming: ``serve_stream`` with the tiny slot decoder, so the decode
    step compiles and steps on the chip; its records must equal
    ``answer_batch``'s.

``--chips 4`` runs only the sharded phase: ``--shards 4 --shard-execution
device`` over 6×10⁶ × 768 f32 (18.4 GB, more than one chip holds), checked
against the float64 reference, with each device's bytes in use.

Each check prints one line; the seconds and bytes printed are smoke
numbers, not benchmark numbers. The last line is the JSON verdict. Any
failed check, and a run where jax finds no TPU, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
PAPER_CELL = ROOT / "results" / "BENCH_serving.json"


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def engine_opts(*cli_args: str) -> dict:
    """Engine options exactly as the serve CLI parses ``cli_args``."""
    from repro.launch.serve import _ENGINE_OPT_KEYS, build_parser

    args = build_parser().parse_args(list(cli_args))
    return {key: getattr(args, key) for key in _ENGINE_OPT_KEYS}


def reference_topk(corpus: np.ndarray, qvecs: np.ndarray, k: int) -> np.ndarray:
    """Exact MIPS in float64: top-k ids per query, ties to the lowest id.

    Queries are normalized as the index normalizes them. The corpus is
    scored in row blocks so no float64 copy of it is ever whole."""
    q = np.asarray(qvecs, np.float64)
    q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-9)
    scores = np.empty((q.shape[0], corpus.shape[0]), np.float64)
    step = 1 << 18
    for s in range(0, corpus.shape[0], step):
        scores[:, s : s + step] = q @ np.asarray(corpus[s : s + step], np.float64).T
    return np.argsort(-scores, axis=1, kind="stable")[:, :k]


def check_sampled_ids(tag: str, engine, queries, responses, n_sample: int):
    """Served ids of ``n_sample`` seeded queries == the float64 reference at
    each query's routed depth. Returns the sample's query vectors and their
    float64 top-10 ids."""
    sample = np.random.default_rng(SEED).choice(len(queries), size=n_sample, replace=False)
    qvecs = np.asarray(engine.embedder.embed([queries[i] for i in sample]), np.float32)
    ref = reference_topk(np.asarray(engine.index.embeddings, np.float32), qvecs, 10)
    n_retrieval = 0
    for row, i in enumerate(sample):
        k = engine.catalog[responses[i].record.bundle].top_k
        # synthetic passages are "synthetic document <id>"
        got = [int(text.rsplit(" ", 1)[1]) for text in responses[i].passages]
        if got != ref[row, :k].tolist():
            raise SmokeFailure(
                f"{tag} query {i}: served ids {got} != float64 reference "
                f"{ref[row, :k].tolist()}"
            )
        n_retrieval += k > 0
    check(n_retrieval > 0, f"{tag} served ids of {n_retrieval} sampled retrieval "
          "queries == float64 reference")
    return qvecs, ref


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def phase_paper(jax) -> None:
    """(a) The CLI-default engine over the paper benchmark."""
    from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
    from repro.launch.serve import build_engine_from_opts

    engine = build_engine_from_opts(engine_opts())
    queries = list(BENCHMARK_QUERIES)
    t0 = time.perf_counter()
    # engine.run is answer_batch plus the telemetry return; the responses
    # carry the passages that the id check needs
    responses = engine.answer_batch(queries, list(REFERENCE_ANSWERS))
    print(f"[a] smoke: answer_batch over {len(queries)} queries, cold "
          f"(compiles included) {time.perf_counter() - t0:.3f} s", flush=True)
    counts = engine.telemetry.strategy_counts()
    want = json.loads(PAPER_CELL.read_text())["catalogs"]["paper"]["routed_by_bundle"]
    check(counts == want, f"[a] strategy_counts {counts} == committed paper cell {want}")

    qvecs = np.asarray(engine.embedder.embed(queries), np.float32)
    ref = reference_topk(np.asarray(engine.index.embeddings, np.float32), qvecs, 10)
    texts = [p.text for p in engine.index.passages]
    n_checked = 0
    for i, resp in enumerate(responses):
        k = engine.catalog[resp.record.bundle].top_k
        expected = [texts[j] for j in ref[i, :k]]
        if resp.passages != expected:
            raise SmokeFailure(
                f"[a] query {i} ({queries[i]!r}): passages differ from the "
                f"float64 reference ids {ref[i, :k].tolist()}"
            )
        n_checked += k > 0
    check(n_checked > 0, f"[a] retrieved passages of {n_checked} retrieval queries "
          "== float64 exact-MIPS reference")


def phase_real_size(jax) -> None:
    """(b) 10⁶ × 768 through the served scorer, then the Pallas kernel."""
    from repro.launch.serve import build_engine_from_opts
    from repro.serving.scenarios import QueryPoolSpec, template_query_pool

    n_docs, dim = 1_000_000, 768
    t0 = time.perf_counter()
    engine = build_engine_from_opts(engine_opts(
        "--synthetic-docs", str(n_docs), "--synthetic-dim", str(dim),
        "--synthetic-seed", str(SEED),
    ))
    print(f"[b] smoke: engine built over {n_docs}x{dim} f32 in "
          f"{time.perf_counter() - t0:.3f} s (host)", flush=True)
    queries, _ = template_query_pool(QueryPoolSpec(n_queries=256, seed=SEED))

    t0 = time.perf_counter()
    responses = engine.answer_batch(queries)
    cold = time.perf_counter() - t0
    # a second engine turn over the same queries: every program is warm
    t0 = time.perf_counter()
    engine.answer_batch(queries)
    warm = time.perf_counter() - t0
    print(f"[b] smoke: answer_batch of {len(queries)} queries, blocked scorer: "
          f"cold {cold:.3f} s, warm {warm:.3f} s, compile ~{cold - warm:.3f} s",
          flush=True)
    counts = engine.telemetry.strategy_counts()
    check(sum(counts.values()) == 2 * len(queries),
          f"[b] {len(queries)} queries served twice, routed {counts}")

    qvecs, ref = check_sampled_ids("[b]", engine, queries, responses, 32)

    index = engine.index
    for k in (3, 5, 10):
        t0 = time.perf_counter()
        _, ids = index.search_batch(qvecs, k, scorer="pallas")
        ids = np.asarray(ids)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, again = index.search_batch(qvecs, k, scorer="pallas")
        again = np.asarray(again)
        warm = time.perf_counter() - t0
        print(f"[b] smoke: pallas mips_topk k={k} over {len(qvecs)} queries: "
              f"cold {cold:.3f} s, warm {warm:.3f} s, compile ~{cold - warm:.3f} s",
              flush=True)
        check(np.array_equal(ids, ref[:, :k]) and np.array_equal(ids, again),
              f"[b] pallas k={k} ids == float64 reference")
    dev = jax.devices()[0]
    print(f"[b] smoke: peak_bytes_in_use {peak_bytes(dev)} "
          f"(corpus {n_docs * dim * 4} bytes)", flush=True)


def phase_stream(jax) -> None:
    """(c) Streaming with the tiny slot decoder on the chip."""
    from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
    from repro.launch.serve import build_engine_from_opts
    from repro.serving.generator import TransformerSlotDecoder
    from repro.serving.streaming import StreamConfig, serve_stream

    opts = engine_opts()
    queries = list(BENCHMARK_QUERIES[:8])
    refs = list(REFERENCE_ANSWERS[:8])
    decoder = TransformerSlotDecoder.tiny(n_slots=8)
    t0 = time.perf_counter()
    decoder.warmup()
    print(f"[c] smoke: decode step compiled in {time.perf_counter() - t0:.3f} s",
          flush=True)
    check(next(iter(jax.tree.leaves(decoder.params))).devices() == {jax.devices()[0]},
          "[c] decoder parameters live on the chip")
    engine = build_engine_from_opts(opts)
    t0 = time.perf_counter()
    result = serve_stream(engine, queries, refs, decode_fn=decoder, config=StreamConfig())
    summary = result.summary()
    print(f"[c] smoke: stream of {len(queries)} requests drained in "
          f"{time.perf_counter() - t0:.3f} s, {summary['decode_steps']} decode steps",
          flush=True)
    check(summary["completed"] == len(queries) and summary["rejected"] == 0,
          f"[c] {summary['completed']}/{len(queries)} requests completed, none rejected")
    check(decoder.steps_run > 0 and summary["decode_steps"] == decoder.steps_run,
          f"[c] the decoder stepped {decoder.steps_run} times on the chip")
    toks = np.asarray(decoder.tokens)
    check(bool(((toks >= 0) & (toks < decoder.cfg.vocab)).all()),
          "[c] decoded tokens lie in the vocabulary")
    ref = build_engine_from_opts(opts)
    ref.answer_batch(queries, refs)
    check(engine.telemetry.to_csv() == ref.telemetry.to_csv(),
          "[c] streamed records == answer_batch records (byte-identical CSV)")


def phase_four_chips(jax) -> None:
    """--chips 4: a corpus one chip cannot hold, sharded over four."""
    from repro.launch.serve import build_engine_from_opts
    from repro.retrieval import DeviceShardedBackend
    from repro.serving.scenarios import QueryPoolSpec, template_query_pool

    n_docs, dim, shards = 6_000_000, 768, 4
    devices = jax.devices()
    check(len(devices) == shards, f"[4] {len(devices)} devices visible")
    t0 = time.perf_counter()
    engine = build_engine_from_opts(engine_opts(
        "--synthetic-docs", str(n_docs), "--synthetic-dim", str(dim),
        "--synthetic-seed", str(SEED), "--shards", str(shards),
        "--shard-execution", "device",
    ))
    print(f"[4] smoke: engine built over {n_docs}x{dim} f32 "
          f"({n_docs * dim * 4} bytes) in {time.perf_counter() - t0:.3f} s (host)",
          flush=True)
    check(isinstance(engine.backends["dense"], DeviceShardedBackend),
          "[4] the dense backend is device-sharded")
    queries, _ = template_query_pool(QueryPoolSpec(n_queries=64, seed=SEED))
    t0 = time.perf_counter()
    responses = engine.answer_batch(queries)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.answer_batch(queries)
    warm = time.perf_counter() - t0
    print(f"[4] smoke: answer_batch of {len(queries)} queries: cold {cold:.3f} s, "
          f"warm {warm:.3f} s, compile+placement ~{cold - warm:.3f} s", flush=True)

    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    quarter = n_docs * dim * 4 / shards
    print(f"[4] smoke: bytes_in_use per device {in_use} (a quarter of the "
          f"corpus is {quarter:.0f})", flush=True)
    check(all(0.9 * quarter < b < 1.25 * quarter for b in in_use),
          "[4] each device holds about a quarter of the corpus, none the whole")

    check_sampled_ids("[4]", engine, queries, responses, 16)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): phases (a)-(c) on one chip; 4: only the "
                    "sharded phase over four chips")
    args = ap.parse_args()

    import jax

    from repro.runtime import enable_compilation_cache

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: jax finds no TPU (default backend {platform!r})",
              file=sys.stderr)
        return 2
    cache = enable_compilation_cache()
    print(f"compilation cache: {cache}", flush=True)
    t_all = time.perf_counter()
    phases = [phase_four_chips] if args.chips == 4 else [
        phase_paper, phase_real_size, phase_stream,
    ]
    for phase in phases:
        t0 = time.perf_counter()
        phase(jax)
        print(f"{phase.__name__}: passed in {time.perf_counter() - t0:.3f} s", flush=True)
    print(f"all phases passed in {time.perf_counter() - t_all:.3f} s", flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
