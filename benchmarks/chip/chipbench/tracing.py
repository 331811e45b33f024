"""Spans around the calls into each layer, and the trace → numbers reduction.

In a ``--trace 1`` run the benchmark wraps, from its own files, the calls
into the program's layers with ``jax.profiler.TraceAnnotation`` spans, so
they land in the profiler's trace on the device's clock:

* ``chipbench.<stage>`` — the five stage functions of
  ``repro.serving.stages`` (both ``answer_batch`` and ``StagePipeline``
  call them through the module);
* ``chipbench.embed`` — the engine's embedder;
* ``chipbench.search`` — the dense backend's ``search_batch``, which also
  counts calls and queries;
* ``chipbench.window`` — one traced segment of the window.

A traced run serves the same window as an untraced one, and :class:`Capture`
traces a few of its units (batches) spread across it, one profiler session
each: the profiler drops device events past a fixed buffer (three traced
30 s windows of ``nq768-batch`` all read the same 10.74 s of device time),
and one batch fits whole. :func:`extract` turns a session's ``.xplane.pb``
into plain events and :func:`reduce` turns the segments' events into the
numbers the metric readers take.
"""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import threading

STAGES = ("route", "retrieve", "assemble", "decode", "finalize")
PREFIX = "chipbench."
# The names the device trace gives the programs that run a dense search:
# the single-device scorers are jax.jit(core), the sharded one
# jax.jit(shard_map(local_search)).
SEARCH_MODULES = ("jit_core", "jit_local_search")
# Units a traced run captures. Four batches spread across nq768-batch's
# window carry its replay load (0.156 dense searches a routed query against
# the window's 0.146 on a v5e chip); one traced run there takes about 215 s.
SEGMENTS = 4


@dataclasses.dataclass
class Counters:
    """Counts taken at the layer boundaries: over the traced segments
    (while ``on``), and of routed queries and searches over the window."""

    routed: int = 0  # queries into route
    embedded: int = 0  # texts into the embedder
    searches: list = dataclasses.field(default_factory=list)  # (nq, k) per search call
    on: bool = False
    window_routed: int = 0
    window_searches: int = 0
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)


class Capture:
    """``capture(i, n)``: a context around unit ``i`` of a window of ``n``
    that traces it, in a profiler session of its own, where it is one of
    :meth:`picked`."""

    def __init__(self, root: pathlib.Path, counters: Counters):
        self.root, self.counters = root, counters
        self.dirs: list[pathlib.Path] = []
        self.units: list[int] = []

    def picked(self, n: int) -> list[int]:
        """:data:`SEGMENTS` units, at the middles of equal parts of the window."""
        m = min(SEGMENTS, n)
        return sorted({(2 * j + 1) * n // (2 * m) for j in range(m)})

    @contextlib.contextmanager
    def __call__(self, i: int, n: int):
        if i not in self.picked(n):
            yield
            return
        import jax
        from jax.profiler import TraceAnnotation

        d = self.root / f"unit-{i}"
        # host spans and device ops only: the Python call tracer would slow
        # every host layer the spans time
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(d), profiler_options=options)
        self.counters.on = True
        try:
            with TraceAnnotation(PREFIX + "window"):
                yield
        finally:
            self.counters.on = False
            jax.profiler.stop_trace()
        self.dirs.append(d)
        self.units.append(i)


@contextlib.contextmanager
def spans(engine, counters: Counters):
    """Wrap the engine's layers in spans for the duration of the block."""
    from jax.profiler import TraceAnnotation

    from repro.serving import stages

    originals = {name: getattr(stages, name) for name in STAGES}

    def wrap_stage(name, fn):
        def stage(engine_, arg, *rest):
            if name == "route":
                with counters.lock:
                    counters.window_routed += len(arg)
                    counters.routed += len(arg) * counters.on
            with TraceAnnotation(PREFIX + name):
                return fn(engine_, arg, *rest)

        return stage

    embedder = engine.embedder
    dense = engine.backends["dense"]
    embed, search = embedder.embed, dense.search_batch

    def traced_embed(texts):
        with counters.lock:
            counters.embedded += len(texts) * counters.on
        with TraceAnnotation(PREFIX + "embed"):
            return embed(texts)

    def traced_search(queries, query_vecs, k):
        with counters.lock:
            counters.window_searches += 1
            if counters.on:
                counters.searches.append((len(queries), int(k)))
        with TraceAnnotation(PREFIX + "search"):
            return search(queries, query_vecs, k)

    for name, fn in originals.items():
        setattr(stages, name, wrap_stage(name, fn))
    embedder.embed, dense.search_batch = traced_embed, traced_search
    try:
        yield
    finally:
        for name, fn in originals.items():
            setattr(stages, name, fn)
        del embedder.embed, dense.search_batch  # the instance attributes


def extract(xplane: pathlib.Path) -> dict:
    """Plain events from a profiler trace: the device planes' ops and
    modules, and the host's ``chipbench.*`` spans per thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    devices: dict[str, dict[str, list]] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                    ]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name[len(PREFIX):], f"{line.name}#{tid}",
                                     float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> tuple[float, float] | None:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _segment(events: dict) -> dict:
    """One traced segment's numbers, in ns (see :func:`reduce`)."""
    windows = [h for h in events["host"] if h[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    lo, hi = windows[0][2], windows[0][2] + windows[0][3]
    spans = [h for h in events["host"] if h[0] != "window"]

    busy_total, search_s = 0.0, 0.0
    op_time: dict[str, float] = {}
    busy_sets = []
    for lines in events["devices"].values():
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        iv = [c for name, s, d in ops if (c := _clip(s, s + d, lo, hi))]
        for name, s, d in ops:
            c = _clip(s, s + d, lo, hi)
            if c:
                op_time[name] = op_time.get(name, 0.0) + (c[1] - c[0])
        u = _union(iv)
        busy_sets.append(u)
        busy_total += sum(e - s for s, e in u)
        for name, s, d in lines.get("XLA Modules", []):
            c = _clip(s, s + d, lo, hi)
            if c and name.split("(")[0] in SEARCH_MODULES:
                search_s += c[1] - c[0]
    n_dev = max(1, len(events["devices"]))
    last_op = max((s + d for lines in events["devices"].values()
                   for _, s, d in lines.get("XLA Modules", [])), default=None)
    late = [s for name, _, s, _ in spans if name == "search" and last_op is not None and s > last_op]
    if late:
        raise RuntimeError(
            f"{len(late)} searches start after the device trace's last program: the "
            "profiler dropped device events; trace shorter units"
        )

    by_thread: dict[str, list] = {}
    for name, thread, s, d in spans:
        by_thread.setdefault(thread, []).append((s, s + d, name))
    self_s: dict[str, float] = {}
    total_s: dict[str, float] = {}
    for items in by_thread.values():
        # spans on one thread nest: a span's direct children are disjoint
        items.sort(key=lambda x: (x[0], -x[1]))
        stack: list = []
        for s, e, name in items:
            while stack and stack[-1][1] <= s:
                stack.pop()
            total_s[name] = total_s.get(name, 0.0) + (e - s)
            self_s[name] = self_s.get(name, 0.0) + (e - s)
            if stack and e <= stack[-1][1]:
                self_s[stack[-1][2]] -= e - s
            stack.append((s, e, name))

    gaps = []
    if busy_sets:
        edges = [lo] + [x for s, e in busy_sets[0] for x in (s, e)] + [hi]
        gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a),
                      reverse=True)[:10]
    every_span = [x for items in by_thread.values() for x in items]
    idle_gaps = []
    for length, a, b in gaps:
        mid = (a + b) / 2
        over = [(e - s, name) for s, e, name in every_span if s <= mid <= e]
        idle_gaps.append([min(over)[1] if over else "no span", length])
    return {
        "window": hi - lo,
        "busy": busy_total / n_dev,
        "search": search_s,
        "self": self_s,
        "total": total_s,
        "ops": op_time,
        "gaps": idle_gaps,
    }


def _add(into: dict, more: dict) -> None:
    for k, v in more.items():
        into[k] = into.get(k, 0.0) + v


def reduce(segments: list[dict]) -> dict:
    """Numbers from the traced segments' :func:`extract` events, in seconds,
    summed over the segments:

    ``window_s``, ``busy_s`` (union of device-op intervals in the window,
    averaged over devices), ``search_device_s`` (device time of the search
    modules, summed over devices), ``self_s`` (per span name: its time
    minus the time of the spans nested in it on its thread), ``total_s``
    (per span name), ``device_ops`` (top 10 ops by total time) and
    ``idle_gaps`` (top 10 gaps between device ops, named by the innermost
    host span over the gap's middle)."""
    if not segments:
        raise ValueError("no traced segment")
    parts = [_segment(events) for events in segments]
    self_s: dict[str, float] = {}
    total_s: dict[str, float] = {}
    op_time: dict[str, float] = {}
    for part in parts:
        _add(self_s, part["self"])
        _add(total_s, part["total"])
        _add(op_time, part["ops"])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted((g for part in parts for g in part["gaps"]), key=lambda g: -g[1])[:10]
    return {
        "window_s": sum(p["window"] for p in parts) / 1e9,
        "busy_s": sum(p["busy"] for p in parts) / 1e9,
        "search_device_s": sum(p["search"] for p in parts) / 1e9,
        "devices": len(segments[0]["devices"]),
        "self_s": {k: v / 1e9 for k, v in self_s.items()},
        "total_s": {k: v / 1e9 for k, v in total_s.items()},
        "device_ops": [[name, t / 1e9] for name, t in top_ops],
        "idle_gaps": [[name, t / 1e9] for name, t in gaps],
    }
