"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root lists the cells. Everything that
belongs to one configuration, one mix or one metric sits in a file of its
own under this benchmark's directory, found by the name alone:

* ``configs/<file>`` — as the configuration's ``file`` entry names it;
* ``traffic/<traffic>.json`` — the mix a cell's ``traffic`` names;
* ``arrivals/<kind>.py`` — the driver of the window the mix's
  ``arrivals.kind`` names: ``warm_sizes(params)``, ``units(params,
  seconds)`` and ``drive(engine, gen, params, seconds, capture)``;
* ``metrics/<metric>.py`` — one reader per metric, ``read(run)``.

So a later cell, mix, arrival kind, configuration or metric is new files
plus entries in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]  # the metrics a --trace 0 run reports
    per_layer: tuple[dict, ...]  # the metrics a --trace 1 run reports


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(
    name: str, *, root: pathlib.Path = ROOT, bench_dir: pathlib.Path = BENCH_DIR
) -> Cell:
    """The cell called ``name``, with its files read."""
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key goes with every cell that
    # reports the end-to-end metric it moves
    per_layer = tuple(
        m
        for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    )
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(metric: str, *, bench_dir: pathlib.Path = BENCH_DIR) -> Callable:
    """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    name = "chipbench_metric_" + metric.replace(".", "_").replace("-", "_")
    return _module(bench_dir / "metrics" / f"{metric}.py", name).read


def driver(kind: str, *, bench_dir: pathlib.Path = BENCH_DIR):
    """The window's driver for a mix's ``arrivals.kind``."""
    return _module(bench_dir / "arrivals" / f"{kind}.py", "chipbench_arrivals_" + kind)


def reference(config: dict, *, bench_dir: pathlib.Path = BENCH_DIR):
    """The plain reference module a configuration names."""
    name = config["reference"]
    return _module(bench_dir / "configs" / f"{name}.py", "chipbench_ref_" + name)
