"""One run of one cell: set up, warm up, measure, check, report."""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import shutil
import sys
import time

from chipbench import check, served, spec, tracing
from chipbench.corpus import make_rows
from chipbench.peaks import peaks_for
from chipbench.traffic import QueryGenerator

TRACE_DIR = spec.ROOT / ".chipbench" / "trace"


@dataclasses.dataclass
class RunData:
    """What a metric reader reads (``metrics/<name>.py``: ``read(run)``)."""

    setup_s: float
    window: served.Window
    rows: int
    dim: int
    peaks: dict
    trace: dict | None = None  # tracing.reduce() of the traced segments
    counters: tracing.Counters | None = None


class CompileCounter:
    """Counts programs jax lowers (each new shape, cached or not)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def enable_cache() -> None:
    """The program's fixed compile-cache directory, every program cached."""
    import jax

    from repro.runtime import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def _untraced(i: int, n: int):
    return contextlib.nullcontext()


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, trace_dir: pathlib.Path = TRACE_DIR,
        bench_dir: pathlib.Path = spec.BENCH_DIR) -> tuple[dict, list[str]]:
    """The result line's object and the check lines, for one run.
    ``t_start`` is the process's start on the ``perf_counter`` clock."""
    import jax

    from repro.retrieval import DenseIndex
    from repro.retrieval.chunking import Passage

    compiles = CompileCounter()
    cfg, mix = cell.config, cell.traffic
    arrivals = dict(mix["arrivals"])
    driver = spec.driver(arrivals.pop("kind"), bench_dir=bench_dir)
    rows, dim = int(cfg["rows"]), int(cfg["dim"])
    device = jax.devices()[0]
    peaks = peaks_for(device.device_kind) if device.platform != "cpu" else {}

    # -- set-up: corpus, engine, warm-up ------------------------------------
    host = make_rows(rows, dim, seed)
    passages = [Passage(i, f"synthetic document {i}") for i in range(rows)]
    index = DenseIndex(host, passages, assume_normalized=True)
    opts = served.serve_options(cfg.get("serve_args", []))
    served.warm_up(served.build_engine(index, passages, dim, opts), driver.warm_sizes(arrivals),
                   QueryGenerator(mix["queries"], seed, "warm-up"))
    engine = served.build_engine(index, passages, dim, opts)
    gen = QueryGenerator(mix["queries"], seed, "window")
    compiled_before = compiles.n
    setup_s = time.perf_counter() - t_start

    # -- the window ------------------------------------------------------------
    counters = tracing.Counters() if trace else None
    capture = tracing.Capture(trace_dir, counters) if trace else _untraced
    with contextlib.ExitStack() as stack:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            stack.enter_context(tracing.spans(engine, counters))
        window = driver.drive(engine, gen, arrivals, seconds, capture)
    in_window = compiles.n - compiled_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())
    reduced = None
    if trace:
        reduced = tracing.reduce([tracing.extract(_xplane(d)) for d in capture.dirs])

    # -- correctness -----------------------------------------------------------
    limits = {"score_gap": float(cfg["limits"]["score_gap"]), "malformed": 0}
    catalog = engine.catalog
    del engine, index  # the program's state, before the reference runs
    numbers = check.retrieval_numbers(
        host, dim, window.responses, catalog, seed, spec.reference(cfg, bench_dir=bench_dir)
    )
    compared = numbers.pop("compared")
    correct = compared > 0 and all(numbers[k] <= limits[k] for k in limits)

    # -- metrics ---------------------------------------------------------------
    data = RunData(setup_s, window, rows, dim, peaks, reduced, counters)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"], bench_dir=bench_dir)(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(peak),
        },
    }
    notes = [f"programs compiled inside the window: {in_window}"]
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        c = counters
        notes.append(
            f"traced units {capture.units} of {driver.units(arrivals, seconds)}: "
            f"{len(c.searches)} dense searches for {c.routed} queries routed "
            f"({len(c.searches) / max(1, c.routed):.4f} a query); the whole window "
            f"{c.window_searches} for {c.window_routed} "
            f"({c.window_searches / max(1, c.window_routed):.4f})"
        )
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    notes += [
        f"answers compared with the reference: {compared}",
        f"window: {window.seconds:.3f} s, {len(window.responses)} answered, set-up {setup_s:.3f} s",
    ]
    lines = [f"check {k}: {numbers[k]!r} limit {limits[k]!r}" for k in limits]
    for line in notes:
        print(line, file=sys.stderr, flush=True)
    return result, lines
