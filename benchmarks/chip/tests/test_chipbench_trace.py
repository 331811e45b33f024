"""The trace → numbers reduction on a small recorded trace of known intervals."""

import json
import pathlib

import pytest

from chipbench import readers, tracing
from chipbench.runner import RunData

DATA = pathlib.Path(__file__).resolve().parent / "data"


def segment():
    return json.loads((DATA / "small_trace.json").read_text())


@pytest.fixture
def reduced():
    return tracing.reduce([segment()])


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(1000e-9)
    # ops 100-300 and 250-400 overlap (300 ns), 620-700 (80), 950-1100 clipped to 1000 (50)
    assert reduced["busy_s"] == pytest.approx(430e-9)
    run = RunData(0.0, None, 1, 1, {}, reduced, tracing.Counters(routed=1))
    assert readers.device_idle_pct(run) == pytest.approx(57.0)


def test_search_device_time_counts_only_search_modules_in_the_window(reduced):
    # jit_core(1) 300 ns + jit_core(2) clipped to 50 ns; jit_other is not a search
    assert reduced["search_device_s"] == pytest.approx(350e-9)


def test_span_self_time_subtracts_nested_spans_per_thread(reduced):
    self_s, total_s = reduced["self_s"], reduced["total_s"]
    assert self_s["route"] == pytest.approx(400e-9)  # 500 minus embed's 100
    assert self_s["embed"] == pytest.approx(100e-9)
    assert self_s["finalize"] == pytest.approx(200e-9)  # 400 minus its retrieve
    # retrieve: 20 ns on the main thread + 50 ns on the worker
    assert self_s["retrieve"] == pytest.approx(70e-9)
    assert total_s["retrieve"] == pytest.approx(500e-9)
    assert self_s["search"] == pytest.approx(430e-9)


def test_idle_gaps_are_named_by_the_innermost_span(reduced):
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps] == ["finalize", "finalize", "embed"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 220e-9, 100e-9])


def test_top_device_ops(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx(250e-9)
    assert ops["fusion.2"] == pytest.approx(150e-9)


def test_searches_served_without_search_device_time_fail_loudly(reduced):
    reduced["search_device_s"] = 0.0
    run = RunData(0.0, None, 10, 4, {}, reduced,
                  tracing.Counters(routed=8, searches=[(8, 5)]))
    with pytest.raises(RuntimeError, match="no device time"):
        readers.search_roofline_pct(run)


def test_a_trace_needs_exactly_one_window():
    ev = segment()
    ev["host"] = [h for h in ev["host"] if h[0] != "window"]
    with pytest.raises(ValueError, match="0 window spans"):
        tracing.reduce([ev])


def test_a_device_trace_that_stops_early_is_refused():
    ev = segment()
    ev["host"].append(["search", "python#0", 990, 5])
    for lines in ev["devices"].values():
        lines["XLA Modules"] = [m for m in lines["XLA Modules"] if m[1] < 900]
    with pytest.raises(RuntimeError, match="dropped device events"):
        tracing.reduce([ev])


def test_segments_add_up(reduced):
    # the same segment again, on another session's clock
    later = segment()
    for lines in later["devices"].values():
        for events in lines.values():
            for e in events:
                e[1] += 10_000
    for h in later["host"]:
        h[2] += 10_000
    two = tracing.reduce([segment(), later])
    for key in ("window_s", "busy_s", "search_device_s"):
        assert two[key] == pytest.approx(2 * reduced[key])
    assert two["self_s"]["route"] == pytest.approx(2 * reduced["self_s"]["route"])
    assert dict(two["device_ops"])["fusion.1"] == pytest.approx(500e-9)
    assert [g[1] for g in two["idle_gaps"]] == pytest.approx([250e-9, 250e-9, 220e-9,
                                                              220e-9, 100e-9, 100e-9])


@pytest.mark.parametrize("n, picked", [(1, [0]), (2, [0, 1]), (14, [1, 5, 8, 12]),
                                       (15, [1, 5, 9, 13]), (30, [3, 11, 18, 26])])
def test_traced_units_are_spread_across_the_window(n, picked):
    assert tracing.Capture(DATA, tracing.Counters()).picked(n) == picked
