"""The served path's own profiler spans, scopes and counters.

* ``repro.*`` spans land in a CPU ``jax.profiler`` trace of one
  ``answer_batch``, with their ids and nesting.
* ``answer_batch`` and :class:`StagePipeline` count the same queries alike
  (:class:`~repro.serving.stages.StageCounts`).
* ``replayed`` counts exactly the re-executions finalize makes.
* The serve CLI prints the engine's counts.
* The search programs carry the ``score``/``select``/``merge`` scopes.
"""

import glob
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.policies import make_policy
from repro.data.benchmark import BENCHMARK_QUERIES, REFERENCE_ANSWERS
from repro.retrieval.index import DenseIndex, search_program
from repro.serving import stages
from repro.serving.engine import EngineConfig, build_paper_engine
from repro.serving.stages import StagePipeline

QUERIES = list(BENCHMARK_QUERIES)
REFS = list(REFERENCE_ANSWERS)


def _engine(**config):
    return build_paper_engine(make_policy("router_default"), config=EngineConfig(**config))


def _spans(trace_dir) -> list[tuple[str, float, float, dict]]:
    """``repro.*`` host spans of the one session under ``trace_dir``."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced ``answer_batch`` of the paper queries, after a warm one
    (which also leaves refined priors that flip routes in the traced one)."""
    engine = _engine()
    engine.answer_batch(QUERIES, REFS)
    qid0 = engine._query_counter
    calls_before = engine.counts.search_calls
    replayed_before = engine.counts.replayed
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        engine.answer_batch(QUERIES, REFS)
    finally:
        jax.profiler.stop_trace()
    return {
        "spans": _spans(trace_dir),
        "qid0": qid0,
        "search_calls": engine.counts.search_calls - calls_before,
        "replayed": engine.counts.replayed - replayed_before,
    }


def test_stage_spans_share_the_batch_ids(traced):
    spans, qid0 = traced["spans"], traced["qid0"]
    names = {s[0] for s in spans}
    assert {f"repro.{stage}" for stage in ("route", "retrieve", "assemble", "decode",
                                           "finalize", "embed", "search", "replay")} <= names
    assert {"repro.search.dispatch", "repro.search.fetch"} <= names
    batch = [s for s in spans if s[0] in ("repro.route", "repro.finalize")]
    assert [s[0] for s in batch] == ["repro.route", "repro.finalize"]
    for _, _, _, ids in batch:
        assert ids == {"qid0": qid0, "n": len(QUERIES)}
    # the batch's own middle stages carry its ids; a replay's carry the query's
    middle = [s for s in spans if s[0] == "repro.retrieve"]
    assert middle[0][3] == {"qid0": qid0, "n": len(QUERIES)}
    replays = [s for s in spans if s[0] == "repro.replay"]
    assert sorted(s[3]["qid0"] for s in middle[1:]) == sorted(r[3]["qid"] for r in replays)
    assert all(s[3]["n"] == 1 for s in middle[1:])


def test_spans_nest_as_the_stages_call_each_other(traced):
    spans = traced["spans"]
    (route,) = [s for s in spans if s[0] == "repro.route"]
    (finalize,) = [s for s in spans if s[0] == "repro.finalize"]
    replays = [s for s in spans if s[0] == "repro.replay"]
    for embed in (s for s in spans if s[0] == "repro.embed"):
        assert _within(embed, route) or any(_within(embed, r) for r in replays)
    assert replays and all(_within(r, finalize) for r in replays)
    searches = [s for s in spans if s[0] == "repro.search"]
    retrieves = [s for s in spans if s[0] == "repro.retrieve"]
    assert all(any(_within(s, r) for r in retrieves) for s in searches)
    for part in (s for s in spans if s[0] in ("repro.search.dispatch", "repro.search.fetch")):
        assert any(_within(part, s) for s in searches)
    # dispatch then fetch, chunk by chunk
    chunks = [(s[0], s[3]["chunk"]) for s in spans if s[0].startswith("repro.search.")]
    assert chunks[:2] == [("repro.search.dispatch", 0), ("repro.search.fetch", 0)]


def test_span_counts_match_the_engine_counts(traced):
    spans = traced["spans"]
    assert sum(s[0] == "repro.search" for s in spans) == traced["search_calls"]
    assert sum(s[0] == "repro.replay" for s in spans) == traced["replayed"] > 0


@pytest.mark.parametrize("batch", [7, 28])
def test_answer_batch_and_pipeline_count_alike(batch):
    engine, piped = _engine(), _engine()
    pipeline = StagePipeline(piped, depth=1)
    for s in range(0, len(QUERIES), batch):
        engine.answer_batch(QUERIES[s : s + batch], REFS[s : s + batch])
        pipeline.submit(QUERIES[s : s + batch], REFS[s : s + batch])
        assert pipeline.poll() is not None
    assert engine.counts.routed == pipeline.counts.routed == len(QUERIES)
    assert engine.counts == pipeline.counts
    assert pipeline.retrieve_calls == engine.counts.search_calls > 0
    assert pipeline.retrieve_calls_by_backend == {"dense": engine.counts.search_calls}


@pytest.mark.parametrize("refine", [True, False])
def test_replayed_counts_each_re_execution(refine, monkeypatch):
    engine = _engine(use_telemetry_refinement=refine)
    engine.answer_batch(QUERIES, REFS)
    executed = []
    real = stages.execute_one

    def spy(engine_, qid, *rest):
        executed.append(qid)
        return real(engine_, qid, *rest)

    monkeypatch.setattr(stages, "execute_one", spy)
    before = engine.counts.replayed
    engine.answer_batch(QUERIES, REFS)
    assert engine.counts.replayed - before == len(executed) == len(set(executed))
    assert (len(executed) > 0) == refine


def test_serve_cli_prints_the_engines_counts(tmp_path):
    from repro.launch.serve import _ENGINE_OPT_KEYS, build_engine_from_opts, build_parser

    args = ["--policy", "router_default"]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args, "--out", str(tmp_path / "s.csv")],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
             "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    opts = vars(build_parser().parse_args(args))
    engine = build_engine_from_opts({key: opts[key] for key in _ENGINE_OPT_KEYS})
    engine.run(QUERIES, REFS)
    c = engine.counts
    assert c.routed == len(QUERIES) and c.replayed > 0
    assert (f"served: {c.routed} routed, {c.replayed} replayed "
            f"({100 * c.replayed / c.routed:.2f}%), "
            f"searches by backend {c.search_calls_by_backend}") in proc.stdout.splitlines()


def test_serve_cli_prints_the_dense_shard_counters(tmp_path):
    from repro.launch.serve import _ENGINE_OPT_KEYS, build_engine_from_opts, build_parser

    args = ["--policy", "router_default", "--shards", "1", "--shard-execution", "device"]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args, "--out", str(tmp_path / "s.csv")],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
             "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    opts = vars(build_parser().parse_args(args))
    engine = build_engine_from_opts({key: opts[key] for key in _ENGINE_OPT_KEYS})
    engine.run(QUERIES, REFS)
    c, shards = engine.counts, engine.backends["dense"].counters
    assert shards.searches == c.search_calls_by_backend["dense"] > 0
    assert (f"served: {c.routed} routed, {c.replayed} replayed "
            f"({100 * c.replayed / c.routed:.2f}%), "
            f"searches by backend {c.search_calls_by_backend}, "
            f"dense shards {shards.as_dict()}") in proc.stdout.splitlines()


def test_sharded_search_dispatches_every_chunk_before_reading_any(tmp_path):
    from repro.retrieval import ShardedBackend

    index = DenseIndex(np.eye(64, dtype=np.float32)[:50], assume_normalized=True)
    dev = ShardedBackend.from_dense(index, n_shards=1, execution="device")
    q = np.ones((20, 64), np.float32)  # three 8-query chunks
    dev.search_batch(None, q, 5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        dev.search_batch(None, q, 5)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    (search,) = [s for s in spans if s[0] == "repro.search"]
    assert search[3] == {"k": 5, "nq": 20}
    parts = [s for s in spans if s[0].startswith("repro.search.")]
    assert [(s[0], s[3]["chunk"]) for s in parts] == (
        [("repro.search.dispatch", c) for c in range(3)]
        + [("repro.search.fetch", c) for c in range(3)])
    assert all(_within(s, search) for s in parts)


def test_search_program_scopes():
    text = search_program(5, 5000).lower(
        jax.ShapeDtypeStruct((5120, 64), jnp.float32), jax.ShapeDtypeStruct((8, 64), jnp.float32)
    ).as_text(debug_info=True)
    scopes = set(re.findall(r'"jit\(core\)/(\w+)/', text))
    assert {"score", "select"} <= scopes
    assert "jit(core)/score/dot_general" in text and "jit(core)/select/top_k" in text


def test_sharded_local_search_scopes():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    index = DenseIndex(np.eye(64, dtype=np.float32)[:50], assume_normalized=True)
    fn, _ = index.sharded_search_fn(mesh, 5, ("data",), n_valid=4000)
    text = fn.lower(
        jax.ShapeDtypeStruct((4096, 64), jnp.float32), jax.ShapeDtypeStruct((8, 64), jnp.float32)
    ).as_text(debug_info=True)
    for op in ("score/dot_general", "select/top_k", "merge/all_gather", "merge/top_k"):
        assert f"jit(local_search)/{op}" in text
