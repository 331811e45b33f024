"""The program's spans and scopes → numbers, on a small hand-written trace."""

import copy
import json
import pathlib

import pytest

from chipbench import program_trace, served, spec, tracing
from chipbench.corpus import make_rows
from chipbench.runner import RunData, _untraced
from chipbench.tracing import Counters
from chipbench.traffic import QueryGenerator

DATA = pathlib.Path(__file__).resolve().parent / "data"


# the fixture's ops by signature; copy.1 runs in jit_other, copy.5 has no scope
SCOPES = {"fusion.2": "score", "sort.1": "select", "while": "select", "fusion.9": "select",
          "copy.5": "", "copy.1": ""}


def segment():
    return json.loads((DATA / "small_program_trace.json").read_text())


@pytest.fixture
def reduced():
    return program_trace.reduce([segment()], SCOPES)


def _run(reduced, monkeypatch, searches=((8, 5), (1, 10))):
    monkeypatch.setattr(program_trace, "read", lambda run: reduced)
    return RunData(0.0, None, 1, 1, {}, {"traced": True}, Counters(searches=list(searches)))


def test_scope_device_time_is_a_union_within_the_search_modules(reduced):
    # score: 100-250 and 900-1000 (clipped); select: 250-380, the while 380-400
    # and its body op inside it; copy.1 runs in jit_other, not a search
    assert reduced["scope_s"]["score"] == pytest.approx(250e-9)
    assert reduced["scope_s"]["select"] == pytest.approx(150e-9)
    assert reduced["scope_s"][""] == pytest.approx(5e-9)
    assert reduced["search_ops_s"] == pytest.approx(400e-9)
    assert reduced["scoped_s"] == pytest.approx(400e-9)


def test_idle_under_search_spans(reduced):
    # search spans 95-420 and 615-985; device busy 100-400, 600-700, 900-1000
    assert reduced["search_idle_s"] == pytest.approx(225e-9)


def test_idle_goes_to_the_innermost_program_span(reduced):
    idle = {k: v * 1e9 for k, v in reduced["idle_by_span_s"].items()}
    assert idle == pytest.approx({"route": 40, "embed": 50, "retrieve": 35,
                                  "search.dispatch": 205, "search.fetch": 20,
                                  "assemble": 50, "decode": 60, "finalize": 40})
    assert sum(idle.values()) == pytest.approx(1000 - 500)


def test_counts_of_routed_replays_and_dispatches(reduced):
    assert (reduced["routed"], reduced["replays"]) == (8, 1)
    assert (reduced["searches"], reduced["dispatches"], reduced["search_modules"]) == (2, 2, 2)
    notes = program_trace.notes(reduced, counted=2)
    assert notes[0].startswith("device idle under each innermost program span (s): "
                               "search.dispatch 0.000000")
    assert notes[1] == ("scoped search op time 0.000000 s of 0.000000 s (100.000%): "
                        "no scope 0.000000, score 0.000000, select 0.000000")
    assert "program searches 2 (the benchmark counted 2)" in notes[2]


@pytest.mark.parametrize("metric, value", [
    ("search_score_ms_per_query.batch", 250e-6 / 9),
    ("search_select_ms_per_query.batch", 150e-6 / 9),
    ("search_idle_ms_per_query.batch", 225e-6 / 9),
    ("replay_share.batch", 12.5),
])
def test_readers(metric, value, reduced, monkeypatch):
    read = spec.reader(metric)
    assert read(_run(reduced, monkeypatch)) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["search_score_ms_per_query.batch",
                                    "search_select_ms_per_query.batch",
                                    "search_idle_ms_per_query.batch", "replay_share.batch"])
def test_readers_find_nothing_in_a_program_without_spans_or_scopes(metric, monkeypatch):
    bare = segment()
    bare["host"] = [h for h in bare["host"] if not h[0].startswith("repro.")]
    read = spec.reader(metric)
    assert read(_run(program_trace.reduce([bare], dict.fromkeys(SCOPES, "")), monkeypatch)) is None


@pytest.mark.parametrize("metric", ["search_score_ms_per_query.batch",
                                    "search_select_ms_per_query.batch"])
def test_scope_readers_report_nothing_where_the_scopes_miss_ops(metric, monkeypatch):
    # fusion.2 (250 of the 400 ns of search op time) has no entry in the map
    missed = program_trace.reduce([segment()], {k: v for k, v in SCOPES.items() if k != "fusion.2"})
    assert missed["scoped_s"] == pytest.approx(150e-9)
    assert "under 95%: the scope readers report nothing" in program_trace.notes(missed, 2)[1]
    assert spec.reader(metric)(_run(missed, monkeypatch)) is None
    # the idle and replay readers do not depend on the scope map
    assert spec.reader("search_idle_ms_per_query.batch")(_run(missed, monkeypatch)) is not None


@pytest.mark.parametrize("name", ["nq768-batch", "hotpot384-batch"])
def test_engine_counts_equal_the_benchmarks_window_counts(name):
    # the program's own counts over a closed-loop window, against the
    # benchmark's counts of the calls into route and the dense backend
    cell = spec.resolve(name)
    seed = 2**33 + 7
    rows, dim = 6000, 48
    from repro.retrieval import DenseIndex
    from repro.retrieval.chunking import Passage

    passages = [Passage(i, f"synthetic document {i}") for i in range(rows)]
    index = DenseIndex(make_rows(rows, dim, seed), passages, assume_normalized=True)
    engine = served.build_engine(index, passages, dim,
                                 served.serve_options(cell.config.get("serve_args", [])))
    engine.answer_batch(QueryGenerator(cell.traffic["queries"], seed, "warm-up").next_batch(64))
    before = (engine.counts.routed, engine.counts.replayed,
              engine.counts.search_calls_by_backend.get("dense", 0))
    counters = Counters()
    with tracing.spans(engine, counters):
        window = spec.driver("closed").drive(
            engine, QueryGenerator(cell.traffic["queries"], seed, "window"),
            {"batch": 64, "batch_s": 1.0}, 3.0, _untraced)
    routed = engine.counts.routed - before[0]
    assert routed == counters.window_routed == len(window.responses) == 3 * 64
    assert engine.counts.search_calls_by_backend["dense"] - before[2] == counters.window_searches
    assert engine.counts.replayed - before[1] > 0


def test_untraced_runs_and_empty_trace_dirs_read_nothing(tmp_path):
    run = RunData(0.0, None, 1, 1, {}, None, None)
    assert program_trace.read(run, tmp_path) is None
    (tmp_path / "unit-1").mkdir()
    traced = RunData(0.0, None, 1, 1, {}, {}, Counters())
    assert program_trace.read(traced, tmp_path) is None


def test_segments_add_up(reduced):
    later = segment()
    for lines in later["devices"].values():
        for events in lines.values():
            for e in events:
                e[1] += 10_000
    for h in later["host"]:
        h[2] += 10_000
    two = program_trace.reduce([segment(), later], SCOPES)
    assert two["scope_s"]["select"] == pytest.approx(2 * reduced["scope_s"]["select"])
    assert two["search_idle_s"] == pytest.approx(2 * reduced["search_idle_s"])
    assert (two["routed"], two["replays"]) == (16, 2)


def test_a_trace_needs_exactly_one_window():
    ev = segment()
    ev["host"].append(copy.deepcopy(ev["host"][0]))
    with pytest.raises(ValueError, match="2 window spans"):
        program_trace.reduce([ev], SCOPES)


def test_extract_reads_span_ids_from_a_profiler_session(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("chipbench.window"):
            with TraceAnnotation("repro.route", qid0=5, n=3):
                pass
            with TraceAnnotation("repro.replay", qid=6):
                pass
            with TraceAnnotation("chipbench.route"):
                pass
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = program_trace.extract(xplane)
    names = sorted(h[0] for h in events["host"])
    assert names == ["chipbench.window", "repro.replay", "repro.route"]
    (route,) = [h for h in events["host"] if h[0] == "repro.route"]
    assert route[4] == {"qid0": 5, "n": 3}
    reduced = program_trace.reduce([events], {})
    assert (reduced["routed"], reduced["replays"], reduced["devices"]) == (3, 1, 0)


def test_an_op_event_and_its_compiled_instruction_share_a_signature():
    # a device op's event name on the chip, and the same instruction as the
    # compiled program prints it
    event = ("%fusion.2 = f32[391,8,1024]{2,1,0:T(8,128)S(1)} fusion(f32[391,1024,768]"
             "{2,1,0:T(8,128)} %bitcast, f32[8,768]{1,0:T(8,128)S(1)} %broadcast_divide_fusion),"
             " kind=kOutput, calls=%fused_computation.10")
    compiled = ("  %fusion.2 = f32[391,8,1024]{2,1,0:T(8,128)S(1)} fusion(%bitcast, "
                "%broadcast_divide_fusion), kind=kOutput, calls=%fused_computation.10, "
                'metadata={op_name="jit(core)/score/dot_general" stack_frame_id=15}')
    tuple_op = ("ROOT %sort.1 = (f32[8,98,4096]{2,0,1:T(8,128)S(1)}, s32[8,98,4096]"
                "{2,0,1:T(8,128)S(1)}) sort(f32[8,98,4096]{2,0,1:T(8,128)S(1)} %bitcast.24)")
    assert program_trace.signature(event) == program_trace.signature(compiled) == (
        "fusion.2 = f32[391,8,1024]{2,1,0:T(8,128)S(1)} fusion")
    assert program_trace.signature(tuple_op).endswith(") sort")


def test_served_scopes_compile_the_search_programs_afresh():
    scopes = program_trace.served_scopes(5000, 64, (3, 5))
    assert {"score", "select"} <= set(scopes.values())
    dots = [sig for sig, scope in scopes.items() if " dot" in sig or "fusion" in sig]
    assert any(scopes[sig] == "score" for sig in dots)


def test_served_scopes_are_not_taken_from_a_cached_build_without_them(tmp_path, monkeypatch):
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    from repro.retrieval.index import search_program

    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {name: getattr(jax.config, name) for name in settings}
    try:
        for name, value in settings.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        # another build of the same program, without the scopes, fills the cache
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            search_program(3, 5000).lower(jax.ShapeDtypeStruct((5120, 64), jnp.float32),
                                          jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile()
        assert {"score", "select"} <= set(program_trace.served_scopes(5000, 64, (3,)).values())
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
