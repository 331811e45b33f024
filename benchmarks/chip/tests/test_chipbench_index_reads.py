"""``index_reads_per_query.batch`` from the program's spans, on small
hand-written traces: the one-chip one and a four-chip row-sharded one."""

import json
import pathlib

import pytest

from chipbench import index_reads, program_trace, runner, sharded_trace, spec
from chipbench.runner import RunData
from chipbench.tracing import Counters

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRIC = "index_reads_per_query.batch"
# trace file → (dispatch spans, queries routed) inside its window
TRACES = {"small_program_trace.json": (2, 8), "small_sharded_program_trace.json": (2, 16)}


def _events(name):
    return json.loads((DATA / name).read_text())


def _traced(tmp_path, monkeypatch, events) -> RunData:
    """A traced run whose one unit directory holds ``events`` as its session."""
    session = tmp_path / "unit-1" / "plugins" / "profile" / "s"
    session.mkdir(parents=True)
    (session / "h.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(index_reads, "extract", lambda path: events)
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path)
    index_reads._load.cache_clear()
    return RunData(0.0, None, 1, 1, {}, {}, Counters(searches=[(8, 5)]))


@pytest.fixture(autouse=True)
def _fresh_cache():
    index_reads._load.cache_clear()
    yield
    index_reads._load.cache_clear()


@pytest.mark.parametrize("name", list(TRACES))
def test_counts_in_the_window(name):
    dispatches, routed = TRACES[name]
    assert index_reads.reduce([_events(name)]) == {"dispatches": dispatches, "routed": routed}
    # segments add up
    assert index_reads.reduce([_events(name)] * 3) == {"dispatches": 3 * dispatches,
                                                      "routed": 3 * routed}


@pytest.mark.parametrize("name", list(TRACES))
def test_the_reader_gives_dispatches_over_routed_queries(name, tmp_path, monkeypatch):
    dispatches, routed = TRACES[name]
    run = _traced(tmp_path, monkeypatch, _events(name))
    assert spec.reader(METRIC)(run) == pytest.approx(dispatches / routed)


@pytest.mark.parametrize("name", list(TRACES))
def test_the_reader_compiles_no_served_program(name, tmp_path, monkeypatch):
    def refuse(*_args, **_kw):
        raise AssertionError("compiled a served program")

    monkeypatch.setattr(program_trace, "served_scopes", refuse)
    monkeypatch.setattr(sharded_trace, "sharded_scopes", refuse)
    run = _traced(tmp_path, monkeypatch, _events(name))
    assert spec.reader(METRIC)(run) is not None


def test_spans_outside_the_window_are_not_counted():
    events = _events("small_sharded_program_trace.json")
    outside = [h for h in events["host"] if h[2] >= 2000]
    assert {h[0] for h in outside} == {"repro.route", "repro.search.dispatch"}
    events["host"] = [h for h in events["host"] if h[2] < 2000]
    assert index_reads.reduce([events]) == index_reads.reduce(
        [_events("small_sharded_program_trace.json")])


def test_a_program_without_the_spans_reads_nothing(tmp_path, monkeypatch):
    events = _events("small_program_trace.json")
    events["host"] = [h for h in events["host"] if h[0] != "repro.search.dispatch"]
    assert spec.reader(METRIC)(_traced(tmp_path, monkeypatch, events)) is None
    events["host"] = [h for h in events["host"] if h[0] != "repro.route"]
    assert spec.reader(METRIC)(_traced(tmp_path / "b", monkeypatch, events)) is None


def test_untraced_runs_and_missing_sessions_read_nothing(tmp_path):
    assert index_reads.read(RunData(0.0, None, 1, 1, {}, None, None), tmp_path) is None
    traced = RunData(0.0, None, 1, 1, {}, {}, Counters(searches=[(8, 5)]))
    assert index_reads.read(traced, tmp_path) is None


def test_a_trace_needs_exactly_one_window():
    events = _events("small_program_trace.json")
    events["host"] = [h for h in events["host"] if h[0] != "chipbench.window"]
    with pytest.raises(ValueError, match="0 window spans"):
        index_reads.reduce([events])


@pytest.mark.parametrize("cell", ["nq768-batch", "hotpot384-batch", "msmarco768-x4-batch"])
def test_every_batch_cell_reports_it(cell):
    (metric,) = [m for m in spec.resolve(cell).per_layer if m["name"] == METRIC]
    assert (metric["unit"], metric["better"], metric["moves"]) == ("reads", "lower",
                                                                  "answered_qps")
