"""The row-sharded cell, ``msmarco768-x4-batch``, on the CPU at a small size.

Tests that need the cell's four devices run in a subprocess of their own
with four virtual CPU devices (jax fixes the device count when it first
starts). The trace reductions read hand-built four-device traces here.
"""

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from chipbench import readers, sharded_trace, spec, tracing
from chipbench.corpus import make_rows
from chipbench.peaks import PEAKS, search_least_seconds
from chipbench.runner import RunData
from chipbench.tracing import Counters

CELL = "msmarco768-x4-batch"
SEED = 2**33 + 99
ROOT = spec.ROOT


def _run4(script: str) -> dict:
    """Run ``script`` with four CPU devices; it prints one JSON line last."""
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/tmp"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": f"{ROOT / 'benchmarks' / 'chip'}{os.pathsep}{ROOT / 'src'}",
    }
    prelude = f"""
        import dataclasses, json
        import numpy as np
        from chipbench import served, spec
        from chipbench.corpus import make_rows
        from repro.retrieval import DenseIndex
        from repro.retrieval.chunking import Passage

        SEED = {SEED}
        def tiny(rows=6000, dim=48):
            cell = spec.resolve({CELL!r})
            return dataclasses.replace(cell, config=dict(cell.config, rows=rows, dim=dim))
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(prelude) + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout[-1500:]}\nSTDERR:\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_serves_its_index_row_sharded_over_four_chips():
    cell = spec.resolve(CELL)
    assert cell.chips == 4 and cell.config["chips"] == 4
    args = cell.config["serve_args"]
    assert args[args.index("--shards") + 1] == "4"
    assert args[args.index("--shard-execution") + 1] == "device"
    assert (cell.config["rows"], cell.config["dim"], cell.config["reduced"]) == (8841823, 768, [])
    # exact f32: 27.2 GB whole, 2,210,816 rows (1,024-row blocks) on each chip
    assert 4 * 8841823 * 768 == 27_162_080_256
    per_chip = math.ceil(math.ceil(8841823 / 4) / 1024) * 1024
    assert per_chip == 2_210_816 and per_chip * 768 * 4 == 6_791_626_752
    words = cell.traffic["queries"]["words"]
    assert words["mean"] == 5.96
    forms = cell.traffic["queries"]["forms"]
    assert sum(f["weight"] for f in forms) == pytest.approx(1.0)
    cue = ("what", "how", "who", "when", "where", "which", "why", "define")
    assert sum(f["weight"] for f in forms if f["pattern"].split()[0] in cue) == pytest.approx(0.5)


def test_engines_over_one_index_share_the_placed_corpus_and_programs():
    out = _run4("""
        import jax
        from chipbench.traffic import QueryGenerator

        compiles = [0]
        def count(event, duration, **_):
            compiles[0] += event == "/jax/core/compile/jaxpr_to_mlir_module_duration"
        jax.monitoring.register_event_duration_secs_listener(count)
        cell = tiny()
        rows, dim = 6000, 48
        passages = [Passage(i, f"synthetic document {i}") for i in range(rows)]
        index = DenseIndex(make_rows(rows, dim, SEED), passages, assume_normalized=True)
        opts = served.serve_options(cell.config["serve_args"])
        gen = QueryGenerator(cell.traffic["queries"], SEED, "window")
        first = served.build_engine(index, passages, dim, opts)
        served.warm_up(first, [64], gen)
        ks = served.routed_depths(first)
        programs = dict(index._sharded_fn_cache)
        second = served.build_engine(index, passages, dim, opts)
        before = compiles[0]
        second.answer_batch(gen.next_batch(64))
        dense = second.backends["dense"]
        print(json.dumps({
            "backend": type(dense).__name__,
            "distinct_backends": dense is not first.backends["dense"],
            "shards": dense.n_shards,
            "placed": len(index._sharded_corpus),
            "programs": len(index._sharded_fn_cache),
            "ks": len(ks),
            "same_programs": all(index._sharded_fn_cache[key] is fn
                                 for key, fn in programs.items()),
            "second_compiles": compiles[0] - before,
            "second_searches": dense.counters.searches,
            "devices": sorted({d.id for c in index._sharded_corpus.values()
                               for d in c.sharding.device_set}),
        }))
    """)
    assert out["backend"] == "DeviceShardedBackend" and out["distinct_backends"]
    assert out["shards"] == 4 and out["devices"] == [0, 1, 2, 3]
    assert out["placed"] == 1  # one copy of the rows on the four devices
    assert out["programs"] == out["ks"] == 3 and out["same_programs"]
    assert out["second_searches"] > 0 and out["second_compiles"] == 0


def test_a_sound_run_is_correct_and_an_altered_answer_is_not():
    out = _run4("""
        from chipbench.runner import run
        from repro.retrieval import DeviceShardedBackend

        sound, lines = run(tiny(), SEED, 0.3, False, 0.0)
        orig = DeviceShardedBackend.search_batch
        def altered(self, queries, query_vecs, k):
            scores, ids = orig(self, queries, query_vecs, k)
            ids = np.asarray(ids).copy()
            ids[:, 0] = (ids[:, 0] + 1) % self.size
            return scores, ids
        DeviceShardedBackend.search_batch = altered
        faulty, _ = run(tiny(), SEED, 0.3, False, 0.0)
        print(json.dumps({"sound": sound, "faulty": faulty, "lines": lines}))
    """)
    sound, faulty = out["sound"], out["faulty"]
    assert sound["correct"], out["lines"]
    assert sound["device"]["count"] == 4 and sound["attempted"] == 256
    assert sound["checks"]["malformed"] == {"value": 0, "limit": 0}
    assert sound["checks"]["score_gap"]["value"] < sound["checks"]["score_gap"]["limit"]
    assert not faulty["correct"]
    assert faulty["checks"]["score_gap"]["value"] > faulty["checks"]["score_gap"]["limit"]


def test_the_control_fails_the_cells_limit():
    """Scores at ``high`` (three bf16 passes) over rows of the cell's width
    read above the limit that float32 at the highest precision reads below."""
    path = spec.BENCH_DIR / "control.py"
    mod_spec = importlib.util.spec_from_file_location("chipbench_control", path)
    control = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(control)
    cell = spec.resolve(CELL)
    cell = dataclasses.replace(cell, config=dict(cell.config, rows=40000))
    corpus = make_rows(40000, 768, SEED)
    assert control.reading(cell, corpus, SEED) > cell.config["limits"]["score_gap"]


def test_the_scope_map_covers_the_served_sharded_program():
    out = _run4("""
        import re
        import jax.numpy as jnp
        from chipbench import program_trace, sharded_trace
        from repro.retrieval import ShardedBackend

        rows, dim = 6000, 48
        index = DenseIndex(make_rows(rows, dim, SEED), assume_normalized=True)
        dev = ShardedBackend.from_dense(index, n_shards=4, execution="device")
        scopes = sharded_trace.sharded_scopes(rows, dim, (3, 5, 10), 4)
        skip = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element", "partition-id"}
        found = {}
        for k in (3, 5, 10):
            dev.search_batch(None, np.ones((8, dim), np.float32), k)
            fn, corpus = index.device_sharded_search(dev.mesh, k, dev.shard_axes)
            text = fn.lower(corpus, jnp.zeros((8, dim), jnp.float32)).compile().as_text()
            sigs = [program_trace.signature(line) for line in text.splitlines()
                    if program_trace._INSTRUCTION.match(line)]
            entry = text[text.index("ENTRY"):]
            entry = entry[: entry.index("\\n}")]
            work = scoped = 0
            for line in entry.splitlines()[1:]:
                m = program_trace._INSTRUCTION.match(line)
                if not m or m[3] in skip:
                    continue
                size = sum(int(np.prod([int(x) for x in dims.split(",") if x] or [1]))
                           for dims in re.findall(r"\\[([\\d,]*)\\]", m[2]))
                work += size
                scoped += size * bool(scopes.get(program_trace.signature(line)))
            found[k] = {"in_map": sum(s in scopes for s in sigs) / len(sigs),
                        "scoped": scoped / work,
                        "scopes": sorted({scopes.get(program_trace.signature(line), "")
                                          for line in entry.splitlines()[1:]} - {""})}
        print(json.dumps(found))
    """)
    for k, got in out.items():
        assert got["in_map"] == 1.0, k  # the map compiled the program the search runs
        assert got["scoped"] >= 0.95, k  # of the entry ops' output elements
        assert got["scopes"] == ["merge", "score", "select"], k


def test_the_sharded_search_writes_dispatch_and_fetch_spans(tmp_path):
    out = _run4(f"""
        import pathlib
        import jax
        from chipbench import program_trace
        from repro.retrieval import ShardedBackend

        index = DenseIndex(make_rows(6000, 48, SEED), assume_normalized=True)
        dev = ShardedBackend.from_dense(index, n_shards=4, execution="device")
        q = np.ones((20, 48), np.float32)
        dev.search_batch(None, q, 5)
        jax.profiler.start_trace({str(tmp_path)!r})
        try:
            dev.search_batch(None, q, 5)
        finally:
            jax.profiler.stop_trace()
        (xplane,) = pathlib.Path({str(tmp_path)!r}).glob("plugins/profile/*/*.xplane.pb")
        host = program_trace.extract(xplane)["host"]
        print(json.dumps(sorted([h[0], h[2], h[2] + h[3], h[4]] for h in host)))
    """)
    (search,) = [s for s in out if s[0] == "repro.search"]
    assert search[3] == {"k": 5, "nq": 20}
    parts = [s for s in out if s[0].startswith("repro.search.")]
    # three 8-query chunks: every dispatch before any read-back, each inside the search
    assert [(s[0], s[3]["chunk"]) for s in parts] == [
        ("repro.search.dispatch", 0), ("repro.search.dispatch", 1), ("repro.search.dispatch", 2),
        ("repro.search.fetch", 0), ("repro.search.fetch", 1), ("repro.search.fetch", 2)]
    assert all(search[1] <= s[1] and s[2] <= search[2] for s in parts)


# -- a hand-built trace of one sharded search on four chips -------------------------
# Each chip runs two dispatches of the program. Chip i scores for 400 + 100·i ns
# and selects for 100 ns, then waits in the merge until the slowest (chip 3)
# is done: merge 350 - 100·i ns, so every chip's module lasts 850 ns.
SCORE = [400 + 100 * i for i in range(4)]
MERGE = [350 - 100 * i for i in range(4)]
SCOPES = {"fusion.score": "score", "sort.select": "select", "all-gather.merge": "merge",
          "copy.other": ""}
STARTS = (1000, 3000)


def _events(dispatch_spans=True, drop=None):
    """The trace in ``program_trace.extract``'s form and in ``tracing.extract``'s."""
    program = {"devices": {}, "host": [["chipbench.window", "main#0", 0.0, 10000.0, {}],
                                       ["repro.search", "main#0", 100.0, 8000.0,
                                        {"k": 5, "nq": 16}]]}
    bench = {"devices": {}, "host": [["window", "main#0", 0.0, 10000.0],
                                     ["search", "main#0", 90.0, 8020.0]]}
    if dispatch_spans:
        for c in range(2):
            program["host"].append(["repro.search.dispatch", "main#0", 150.0 + 50 * c, 40.0,
                                     {"chunk": c}])
    for i in range(4):
        ops, mods = [], []
        for m, t in enumerate(STARTS):
            if drop == (i, m):
                continue
            mods.append([f"jit_local_search({m})", float(t), 850.0])
            ops += [["fusion.score", float(t), float(SCORE[i])],
                    ["sort.select", float(t + SCORE[i]), 100.0],
                    ["all-gather.merge", float(t + SCORE[i] + 100), float(MERGE[i])]]
        ops.append(["copy.other", 9000.0, 100.0])  # outside the search modules
        plane = f"/device:TPU:{i}"
        program["devices"][plane] = {"ops": ops, "modules": mods}
        bench["devices"][plane] = {"XLA Ops": ops, "XLA Modules": mods}
    return program, bench


def _run(monkeypatch, reduced, trace=None):
    monkeypatch.setattr(sharded_trace, "read", lambda run: reduced)
    return RunData(0.0, None, 1000, 64, PEAKS["TPU v5 lite"], trace or {},
                   Counters(searches=[(16, 5)]))


def test_per_chip_reduction():
    reduced = sharded_trace.reduce([_events()[0]], SCOPES)
    assert reduced["dispatches"] == 2
    chips = reduced["chips"]
    assert sorted(chips) == [f"/device:TPU:{i}" for i in range(4)]
    for i in range(4):
        c = chips[f"/device:TPU:{i}"]
        assert c["modules"] == 2
        assert c["scope_s"]["score"] == pytest.approx(2 * SCORE[i] * 1e-9)
        assert c["scope_s"]["merge"] == pytest.approx(2 * MERGE[i] * 1e-9)
        assert c["search_ops_s"] == c["scoped_s"] == pytest.approx(1700e-9)
        assert c["local_s"] == pytest.approx(2 * (SCORE[i] + 100) * 1e-9)
    assert sharded_trace.notes(reduced)[0].startswith(
        "sharded search: 4 chips, scoped op time 0.000007 s of 0.000007 s (100.000%)")


@pytest.mark.parametrize("metric, value", [
    # the mean over the chips of two dispatches' time, per query of the 16 searched
    ("search_score_ms_per_query.sharded", 2 * 550e-6 / 16),
    ("search_select_ms_per_query.sharded", 2 * 100e-6 / 16),
    ("search_merge_ms_per_query.sharded", 2 * 200e-6 / 16),
    # outside merge: 1000, 1200, 1400, 1600 ns; the busiest over the mean
    ("search_shard_skew.sharded", 100 * 1600 / 1300 - 100),
])
def test_readers(metric, value, monkeypatch):
    run = _run(monkeypatch, sharded_trace.reduce([_events()[0]], SCOPES))
    assert spec.reader(metric)(run) == pytest.approx(value)


def test_search_roofline_is_a_share_of_the_four_chips_roofline():
    """The least time of the whole corpus's bytes at one chip's HBM rate, over
    the search modules' device time summed over the chips."""
    trace = tracing.reduce([_events()[1]])
    assert trace["search_device_s"] == pytest.approx(4 * 2 * 850e-9)
    run = RunData(0.0, None, 1000, 64, PEAKS["TPU v5 lite"], trace,
                  Counters(searches=[(16, 5)]))
    least = search_least_seconds(16, 5, 1000, 64, PEAKS["TPU v5 lite"])
    assert readers.search_roofline_pct(run) == pytest.approx(100 * least / 6800e-9)
    assert spec.reader("search_roofline.batch")(run) == readers.search_roofline_pct(run)


@pytest.mark.parametrize("metric", ["search_score_ms_per_query.sharded",
                                    "search_select_ms_per_query.sharded",
                                    "search_merge_ms_per_query.sharded",
                                    "search_shard_skew.sharded"])
def test_readers_report_nothing_where_the_map_misses_ops_or_nothing_ran(metric, monkeypatch):
    missed = sharded_trace.reduce([_events()[0]], {k: v for k, v in SCOPES.items()
                                                   if k != "fusion.score"})
    assert "under 95%" in sharded_trace.notes(missed)[0]
    assert spec.reader(metric)(_run(monkeypatch, missed)) is None
    empty = {"dispatches": 0, "chips": {}}
    assert spec.reader(metric)(_run(monkeypatch, empty)) is None


def test_a_chip_that_lost_device_events_fails_loudly():
    program, _ = _events(drop=(2, 1))
    with pytest.raises(RuntimeError, match="TPU:2 holds 1 jit_local_search events for 2"):
        sharded_trace.reduce([program], SCOPES)
    # a program that writes no dispatch spans on its sharded path is read unchecked
    program, _ = _events(dispatch_spans=False, drop=(2, 1))
    reduced = sharded_trace.reduce([program], SCOPES)
    assert reduced["chips"]["/device:TPU:2"]["modules"] == 1


def test_untraced_runs_and_cpu_traces_read_nothing(tmp_path, monkeypatch):
    assert sharded_trace.read(RunData(0.0, None, 1, 1, {}, None, None), tmp_path) is None
    traced = RunData(0.0, None, 1, 1, {}, {}, Counters(searches=[(8, 5)]))
    assert sharded_trace.read(traced, tmp_path) is None
    # a session whose device planes hold no sharded program (a CPU run)
    program, _ = _events()
    program["devices"] = {}
    unit = tmp_path / "unit-1" / "plugins" / "profile" / "s"
    unit.mkdir(parents=True)
    (unit / "h.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(sharded_trace, "extract", lambda path: program)
    sharded_trace._load.cache_clear()
    assert sharded_trace.read(traced, tmp_path) == {"dispatches": 0, "chips": {}}
    sharded_trace._load.cache_clear()


def test_the_benchmark_lists_the_new_metrics_for_the_cell_only():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = [m for m in bench["per_layer"] if m["name"].endswith(".sharded")]
    assert [m["name"] for m in new] == [
        "search_score_ms_per_query.sharded", "search_select_ms_per_query.sharded",
        "search_merge_ms_per_query.sharded", "search_shard_skew.sharded"]
    assert all(m["workloads"] == [CELL] for m in new)
    names = {m["name"] for m in spec.resolve(CELL).per_layer}
    # their readers need the single-device program's scope map
    assert not names & {"search_score_ms_per_query.batch", "search_select_ms_per_query.batch"}
    assert pathlib.Path(spec.BENCH_DIR / "metrics" / "search_shard_skew.sharded.py").exists()
