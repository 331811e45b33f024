"""Index reads per routed query, from the program's spans alone.

Each ``repro.search.dispatch`` span is one dispatch of a search program
over one fixed-width query chunk: one read of the whole index, on one chip
or, on the row-sharded path, on every chip at once. This module counts
those spans and the queries routed (the ``n`` of ``repro.route`` spans)
inside each traced segment's window, with the same window clipping as
``program_trace``. It reads host spans only and compiles nothing, so a cell
whose served program the scope maps cannot compile reads it too.

A program without these spans reduces to zeros, and the reader returns
``None``.
"""

from __future__ import annotations

import functools
import pathlib

from chipbench.program_trace import PREFIX, extract
from chipbench.tracing import PREFIX as BENCH
from chipbench.tracing import _clip


def segment(events: dict) -> dict:
    """One traced segment's dispatch spans and routed queries."""
    windows = [h for h in events["host"] if h[0] == BENCH + "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    lo, hi = windows[0][2], windows[0][2] + windows[0][3]
    inside = [(n, a) for n, _, s, d, a in events["host"] if _clip(s, s + d, lo, hi)]
    return {
        "dispatches": sum(1 for n, _ in inside if n == PREFIX + "search.dispatch"),
        "routed": sum(a.get("n", 0) for n, a in inside if n == PREFIX + "route"),
    }


def reduce(segments: list[dict]) -> dict:
    """The segments' counts summed."""
    if not segments:
        raise ValueError("no traced segment")
    parts = [segment(events) for events in segments]
    return {key: sum(p[key] for p in parts) for key in ("dispatches", "routed")}


@functools.lru_cache(maxsize=1)
def _load(files: tuple[tuple[str, int], ...]) -> dict:
    return reduce([extract(pathlib.Path(f)) for f, _ in files])


def read(run, trace_dir: pathlib.Path | None = None) -> dict | None:
    """The counts of the run's traced segments (``None`` untraced): the last
    session under each unit directory, as ``program_trace.read`` finds them."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from chipbench.runner import TRACE_DIR as trace_dir
    files = []
    for unit in sorted(trace_dir.glob("unit-*")):
        found = sorted(unit.glob("plugins/profile/*/*.xplane.pb"))
        if found:
            files.append((str(found[-1]), found[-1].stat().st_mtime_ns))
    return _load(tuple(files)) if files else None


def reads_per_query(run) -> float | None:
    """Search dispatches over queries routed in the traced segments."""
    reduced = read(run)
    if reduced is None or not reduced["routed"] or not reduced["dispatches"]:
        return None
    return reduced["dispatches"] / reduced["routed"]
