"""Every cell resolves to its files, and a cell is added by new files alone."""

import dataclasses
import json
import shutil

import pytest

from chipbench import runner, spec

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(workload):
    cell = spec.resolve(workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    assert cell.config["name"] == w["config"]
    assert cell.traffic["name"] == w["traffic"]
    assert cell.chips == w["chips"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))
    for m in cell.per_layer:  # each moves a metric its cells report
        assert m["moves"] in names
    assert hasattr(spec.reference(cell.config), "topk")


def test_configuration_files_state_source_reduced_assumed():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["assumed"] and cfg["limits"]["score_gap"] > 0


def tiny(cell, rows=4096, dim=32):
    return dataclasses.replace(cell, config=dict(cell.config, rows=rows, dim=dim))


# a new arrival kind: one caller, two calls of a batch each, whatever the seconds
TWO_CALLS = """
from chipbench.served import Window


def warm_sizes(params):
    return [int(params["batch"])]


def units(params, seconds):
    return 2


def drive(engine, gen, params, seconds, capture):
    queries, responses = [], []
    for i in range(2):
        with capture(i, 2):
            qs = gen.next_batch(int(params["batch"]))
            responses += engine.answer_batch(qs)
            queries += qs
    return Window(1.0, queries, responses, len(queries), len(queries) - len(responses))
"""


def test_a_cell_is_added_by_new_files_alone(tmp_path):
    """A new configuration, mix, arrival kind, metric and cell: files and
    entries are added, no file of the benchmark changes, and the new cell
    runs."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(spec.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = json.loads((bench_dir / "configs" / "beir-nq-768.json").read_text())
    cfg.update(name="tiny-64", rows=3000, dim=64)
    (bench_dir / "configs" / "tiny-64.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "nq-questions-batch.json").read_text())
    mix.update(name="tiny-batch", arrivals={"kind": "two-calls", "batch": 32})
    (bench_dir / "traffic" / "tiny-batch.json").write_text(json.dumps(mix))
    (bench_dir / "arrivals" / "two-calls.py").write_text(TWO_CALLS)
    (bench_dir / "metrics" / "queries_per_batch.tiny.py").write_text(
        "def read(run):\n    return run.window.attempted / max(1, len(run.window.queries) // 32)\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-64", "source": cfg["source"],
                             "file": "benchmarks/chip/configs/tiny-64.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny-batch", "config": "tiny-64",
                               "traffic": "tiny-batch", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny-batch")
    bench["per_layer"].append({"name": "queries_per_batch.tiny", "unit": "queries",
                               "better": "higher", "source": "program_counter",
                               "layer": "route", "moves": "answered_qps",
                               "workloads": ["tiny-batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    for p, data in before.items():
        assert p.read_bytes() == data
    cell = spec.resolve("tiny-batch", root=root, bench_dir=bench_dir)
    assert [m["name"] for m in cell.per_layer] == ["queries_per_batch.tiny"]
    assert spec.reader("queries_per_batch.tiny", bench_dir=bench_dir)(
        runner.RunData(0.0, type("W", (), {"attempted": 64, "queries": [0] * 64})(),
                       1, 1, {})) == 32.0
    result, _ = runner.run(cell, 2**32 + 3, 0.5, False, 0.0, bench_dir=bench_dir)
    assert result["correct"] and set(result["metrics"]) == {"answered_qps", "setup_s"}
    assert result["attempted"] == 64
    assert result["metrics"]["answered_qps"]["value"] == 64.0
