"""The program's own spans and scopes in a traced run, reduced to numbers.

The served path writes ``repro.*`` profiler spans itself (``repro.route``,
``repro.embed``, ``repro.retrieve``, ``repro.search`` with its per-chunk
``repro.search.dispatch`` and ``repro.search.fetch``, ``repro.replay``,
...), and its search programs name their ops with ``jax.named_scope``:
``score``, ``select`` and, in the sharded program, ``merge``. This module
reads them from the profiler sessions :class:`tracing.Capture` writes, with
the same segments and window clipping as ``tracing._segment``:

* ``scope_s`` — per scope, the device time of the search modules' ops
  whose scope path holds it (:func:`served_scopes`), as a union of
  intervals so an op nested in a ``while`` is not counted twice;
* ``search_ops_s`` — the union of all the search modules' op intervals,
  and ``scoped_s`` the part of it whose ops have a scope: where that is
  under :data:`COVERED` of it, the scope map missed ops and the scope
  readers report nothing rather than a smaller number;
* ``search_idle_s`` — device-idle time (outside the union of every
  device op) that overlaps ``repro.search`` spans;
* ``routed``, ``replays`` — queries routed (the ``n`` of ``repro.route``
  spans) and re-executions in finalize (``repro.replay`` spans);
* ``searches`` — ``repro.search`` spans;
* ``dispatches``, ``search_modules`` — ``repro.search.dispatch`` spans and
  search module events, which match unless the profiler dropped device
  events;
* ``idle_by_span`` — device-idle time under each innermost program span.

A program without these spans or scopes reduces to zeros, and its readers
return ``None``.
"""

from __future__ import annotations

import bisect
import functools
import pathlib
import re
import sys

from chipbench.readers import _searched
from chipbench.tracing import PREFIX as WINDOW_PREFIX
from chipbench.tracing import SEARCH_MODULES, _clip, _union

PREFIX = "repro."
SCOPES = ("score", "select", "merge")
COVERED = 0.95  # least share of search op time the scopes cover for their readers


def _scope(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return ""


# "%fusion.2 = f32[391,8,1024]{2,1,0:T(8,128)S(1)} fusion(...), ..." → name, shape, opcode
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")


def signature(hlo: str) -> str:
    """An HLO instruction's name, result shape and opcode: a device op
    event's name on the chip is the instruction's text without metadata."""
    m = _INSTRUCTION.match(hlo)
    return f"{m[1]} = {m[2]} {m[3]}" if m else hlo


def served_scopes(rows: int, dim: int, ks) -> dict[str, str]:
    """Instruction signature → scope in the single-device search programs
    a batch cell serves (each ``k`` of ``ks`` over ``rows`` × ``dim``),
    compiled here with their metadata in the persistent cache's key. The
    cache keys a program without its metadata by default, so the executable
    a run loaded may be another build's, with that build's op names; its
    optimized HLO, instruction names included, is this compile's. A
    signature whose scope differs between the programs maps to ``""``; a
    fusion's ``op_name`` is its root op's."""
    import jax
    import jax.numpy as jnp

    from repro.retrieval.index import Q_BLOCK, _block_width, search_program

    found: dict[str, set[str]] = {}
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        for k in sorted({min(k, rows) for k in ks}):
            padded = rows + (-rows) % _block_width(k)
            text = search_program(k, rows).lower(
                jax.ShapeDtypeStruct((padded, dim), jnp.float32),
                jax.ShapeDtypeStruct((Q_BLOCK, dim), jnp.float32),
            ).compile().as_text()
            for line in text.splitlines():
                if _INSTRUCTION.match(line):
                    op_name = re.search(r'op_name="([^"]*)"', line)
                    found.setdefault(signature(line), set()).add(
                        _scope(op_name[1]) if op_name else "")
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)
    return {sig: scopes.pop() if len(scopes) == 1 else "" for sig, scopes in found.items()}


def extract(xplane: pathlib.Path) -> dict:
    """Plain events from a profiler session: each device plane's ops (by
    instruction signature) and modules, the ``repro.*`` host spans with
    their ids, and the benchmark's window span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    devices: dict[str, dict[str, list]] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lines["ops"] = [[signature(e.name), float(e.start_ns), float(e.duration_ns)]
                                    for e in line.events]
                elif line.name == "XLA Modules":
                    lines["modules"] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                                        for e in line.events]
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX) or e.name == WINDOW_PREFIX + "window":
                        args = {k: v for k, v in e.stats if isinstance(v, int)}
                        host.append([e.name, f"{line.name}#{tid}", float(e.start_ns),
                                     float(e.duration_ns), args])
    return {"devices": devices, "host": host}


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _idle_by_span(gaps, spans) -> dict[str, float]:
    """Idle time under the innermost (shortest) program span over it;
    ``spans`` are ``(start, end, name)``, sorted by start."""
    out: dict[str, float] = {}
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    for a, b in gaps:
        # spans that can overlap [a, b]: those starting before b and after a - longest
        lo_i = bisect.bisect_left(starts, a - longest)
        hi_i = bisect.bisect_left(starts, b)
        over = [(s, e, n) for s, e, n in spans[lo_i:hi_i] if e > a]
        cuts = sorted({a, b, *(x for s, e, _ in over for x in (s, e) if a < x < b)})
        for x, y in zip(cuts, cuts[1:]):
            cover = [(e - s, n) for s, e, n in over if s <= x and e >= y]
            name = min(cover)[1] if cover else "no span"
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def segment(events: dict, scopes: dict[str, str]) -> dict:
    """One traced segment's numbers, in ns (see the module's docstring);
    ``scopes`` maps an op's signature to its scope."""
    windows = [h for h in events["host"] if h[0] == WINDOW_PREFIX + "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    lo, hi = windows[0][2], windows[0][2] + windows[0][3]
    spans = [h for h in events["host"] if h[0].startswith(PREFIX)]

    busy_iv: list[tuple[float, float]] = []
    search_iv: list[tuple[float, float]] = []
    scope_iv: dict[str, list] = {}
    search_modules = 0
    for lines in events["devices"].values():
        mods = sorted((s, s + d) for name, s, d in lines.get("modules", [])
                      if name.split("(")[0] in SEARCH_MODULES)
        search_modules += sum(1 for s, e in mods if _clip(s, e, lo, hi))
        mod_starts = [s for s, _ in mods]
        for op, s, d in lines.get("ops", []):
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            busy_iv.append(c)
            # an op belongs to the search module whose interval holds its middle
            mid = (c[0] + c[1]) / 2
            j = bisect.bisect_right(mod_starts, mid) - 1
            if j >= 0 and mid <= mods[j][1]:
                search_iv.append(c)
                scope_iv.setdefault(scopes.get(op, ""), []).append(c)
    n_dev = max(1, len(events["devices"]))
    busy = _union(busy_iv)
    scoped_iv = [c for scope, v in scope_iv.items() if scope for c in v]

    in_window = [(n, s, d, a) for n, _, s, d, a in spans if _clip(s, s + d, lo, hi)]
    search_spans = _union([c for n, s, d, _ in in_window if n == PREFIX + "search"
                           if (c := _clip(s, s + d, lo, hi))])
    search_idle = _length(search_spans) - _overlap(search_spans, busy)
    program = sorted((max(s, lo), min(s + d, hi), n[len(PREFIX):]) for n, s, d, _ in in_window)
    return {
        "window": hi - lo,
        "scope": {k: _length(_union(v)) / n_dev for k, v in scope_iv.items()},
        "search_ops": _length(_union(search_iv)) / n_dev,
        "scoped": _length(_union(scoped_iv)) / n_dev,
        "search_idle": search_idle,
        "routed": sum(a.get("n", 0) for n, _, _, a in in_window if n == PREFIX + "route"),
        "replays": sum(1 for n, *_ in in_window if n == PREFIX + "replay"),
        "searches": sum(1 for n, *_ in in_window if n == PREFIX + "search"),
        "dispatches": sum(1 for n, *_ in in_window if n == PREFIX + "search.dispatch"),
        "search_modules": search_modules,
        "idle_by_span": _idle_by_span(_gaps(busy, lo, hi), program) if program else {},
    }


def reduce(segments: list[dict], scopes: dict[str, str]) -> dict:
    """The segments' numbers summed, times in seconds."""
    if not segments:
        raise ValueError("no traced segment")
    parts = [segment(events, scopes) for events in segments]
    out: dict = {"devices": len(segments[0]["devices"]), "scope_s": {}, "idle_by_span_s": {}}
    for p in parts:
        for k, v in p["scope"].items():
            out["scope_s"][k] = out["scope_s"].get(k, 0.0) + v / 1e9
        for k, v in p["idle_by_span"].items():
            out["idle_by_span_s"][k] = out["idle_by_span_s"].get(k, 0.0) + v / 1e9
    for key in ("window", "search_ops", "scoped", "search_idle"):
        out[key + "_s"] = sum(p[key] for p in parts) / 1e9
    for key in ("routed", "replays", "searches", "dispatches", "search_modules"):
        out[key] = sum(p[key] for p in parts)
    return out


def notes(reduced: dict, counted: int) -> list[str]:
    """What a traced run prints about the program's spans; ``counted`` is
    the dense searches the benchmark counted in the same segments."""
    searches = f"program searches {reduced['searches']} (the benchmark counted {counted})"
    if not reduced["devices"]:
        return [searches + "; the trace holds no device plane"]
    idle = sorted(reduced["idle_by_span_s"].items(), key=lambda kv: -kv[1])
    scoped, ops = reduced["scoped_s"], reduced["search_ops_s"]
    short = "" if _covered(reduced) else (
        f"; under {COVERED:.0%}: the scope readers report nothing")
    return [
        "device idle under each innermost program span (s): "
        + ", ".join(f"{name} {t:.6f}" for name, t in idle),
        f"scoped search op time {scoped:.6f} s of {ops:.6f} s "
        f"({100 * scoped / ops if ops else 0:.3f}%{short}): "
        + ", ".join(f"{scope or 'no scope'} {t:.6f}" for scope, t in sorted(reduced["scope_s"].items())),
        f"{searches}; search dispatches {reduced['dispatches']}, search module events "
        f"{reduced['search_modules']} (fewer events than dispatches: the profiler "
        "dropped device events)",
    ]


def _covered(reduced: dict) -> bool:
    return reduced["scoped_s"] >= COVERED * reduced["search_ops_s"]


@functools.lru_cache(maxsize=1)
def _load(files: tuple[tuple[str, int], ...], counted: int, rows: int, dim: int,
          ks: tuple[int, ...]) -> dict:
    segments = [extract(pathlib.Path(f)) for f, _ in files]
    on_device = ks and any(events["devices"] for events in segments)
    reduced = reduce(segments, served_scopes(rows, dim, ks) if on_device else {})
    for line in notes(reduced, counted):
        print(line, file=sys.stderr, flush=True)
    return reduced


def read(run, trace_dir: pathlib.Path | None = None) -> dict | None:
    """The reduction of the run's traced segments (``None`` untraced): the
    last session under each of the trace directory's unit directories,
    which the traced window wrote afresh."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from chipbench.runner import TRACE_DIR as trace_dir
    files = []
    for unit in sorted(trace_dir.glob("unit-*")):
        found = sorted(unit.glob("plugins/profile/*/*.xplane.pb"))
        if found:
            files.append((str(found[-1]), found[-1].stat().st_mtime_ns))
    if not files:
        return None
    ks = tuple(sorted({k for _, k in run.counters.searches}))
    return _load(tuple(files), len(run.counters.searches), run.rows, run.dim, ks)


def scope_ms_per_query(run, scope: str) -> float | None:
    """Device time of the search ops in ``scope``, ms per query searched;
    ``None`` where the scopes cover under :data:`COVERED` of the search op
    time, since the ops the scope map missed would read as a gain."""
    reduced = read(run)
    if reduced is None or not reduced["devices"] or not run.counters.searches:
        return None
    if not _covered(reduced):
        return None
    t = reduced["scope_s"].get(scope, 0.0)
    return t * 1e3 / _searched(run) if t > 0 else None


def search_idle_ms_per_query(run) -> float | None:
    """Device-idle time under ``repro.search`` spans, ms per query searched."""
    reduced = read(run)
    if reduced is None or not reduced["devices"] or not reduced["searches"]:
        return None
    return reduced["search_idle_s"] * 1e3 / _searched(run)


def replay_share_pct(run) -> float | None:
    """Re-executions in finalize over queries routed, percent."""
    reduced = read(run)
    if reduced is None or not reduced["routed"]:
        return None
    return 100.0 * reduced["replays"] / reduced["routed"]
