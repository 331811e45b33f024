"""The row-sharded search program in a traced run, chip by chip.

A cell whose dense backend is row-sharded over the chips of a mesh
(``--shard-execution device``) runs one program, ``jit_local_search``
(``DenseIndex.sharded_search_fn``), on every chip at once: each chip scores
and selects over its own rows (scopes ``score`` and ``select``), then the
chips' candidate lists meet in an all-gather and a last top-k (scope
``merge``). This module reads the profiler sessions that
:class:`tracing.Capture` writes, with the same window clipping as
``program_trace``, and keeps each chip's numbers apart:

* ``scope_s`` — per chip and scope, the device time of the program's ops
  whose scope path holds it (:func:`sharded_scopes`), a union of intervals;
* ``search_ops_s`` and ``scoped_s`` — per chip, the union of the program's
  op intervals and the part of it whose ops have a scope: where the scoped
  part is under ``program_trace.COVERED`` of it over all chips, the scope
  map missed ops and the readers report nothing;
* ``local_s`` — per chip, the union of the program's op intervals outside
  ``merge``: the work the chip does on its own rows. The skew is read from
  it, since the all-gather waits for the slowest chip and so lengthens the
  merge of every other chip by what the slowest took longer;
* ``modules`` and ``dispatches`` — per chip, the program's module events,
  and the ``repro.search.dispatch`` spans. Every chip runs every dispatch,
  so a chip with another count than the dispatches lost device events to
  the profiler, and the reduction raises. A program that writes no
  dispatch spans on its sharded path is not checked.

A run that served no sharded search reduces to no chips, and the readers
return ``None``.
"""

from __future__ import annotations

import bisect
import functools
import math
import pathlib
import re
import sys

from chipbench.program_trace import COVERED, _INSTRUCTION, _length, _scope, extract, signature
from chipbench.program_trace import PREFIX as PROGRAM
from chipbench.readers import _searched
from chipbench.tracing import PREFIX as BENCH
from chipbench.tracing import _clip, _union

MODULE = "jit_local_search"


def sharded_scopes(rows: int, dim: int, ks, n_shards: int) -> dict[str, str]:
    """Instruction signature → scope in the sharded search programs a cell
    serves (each ``k`` of ``ks`` over ``rows`` × ``dim`` row-sharded over
    the first ``n_shards`` devices, as the serve CLI's ``--shards`` places
    it), compiled here with their metadata in the persistent cache's key,
    as ``program_trace.served_scopes`` compiles the single-device program.
    A signature whose scope differs between the programs maps to ``""``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed import corpus_mesh
    from repro.retrieval import DenseIndex
    from repro.retrieval.index import Q_BLOCK, _block_width

    mesh = corpus_mesh(n_shards)
    axes = tuple(mesh.axis_names)
    # sharded_search_fn builds the program from its arguments alone
    owner = DenseIndex(np.zeros((1, dim), np.float32), assume_normalized=True)
    found: dict[str, set[str]] = {}
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        for k in sorted({min(k, rows) for k in ks}):
            bn = _block_width(k)
            padded = math.ceil(math.ceil(rows / n_shards) / bn) * bn * n_shards
            fn, _ = owner.sharded_search_fn(
                mesh, k, axes, n_valid=rows if padded != rows else None)
            text = fn.lower(
                jax.ShapeDtypeStruct((padded, dim), jnp.float32,
                                     sharding=NamedSharding(mesh, P(axes, None))),
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


def _covered_by(by_scope: dict[str, list], keep) -> float:
    """Length of the union of the op intervals whose scope ``keep`` takes."""
    return _length(_union([c for sc, v in by_scope.items() if keep(sc) for c in v]))


def segment(events: dict, scopes: dict[str, str]) -> dict:
    """One traced segment's numbers, in ns: the dispatch spans, and per chip
    that ran the sharded program (see the module's docstring)."""
    windows = [h for h in events["host"] if h[0] == BENCH + "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    lo, hi = windows[0][2], windows[0][2] + windows[0][3]
    dispatches = sum(1 for n, _, s, d, _ in events["host"]
                     if n == PROGRAM + "search.dispatch" and _clip(s, s + d, lo, hi))
    chips = {}
    for plane, lines in events["devices"].items():
        mods = sorted((s, s + d) for name, s, d in lines.get("modules", [])
                      if name.split("(")[0] == MODULE and _clip(s, s + d, lo, hi))
        if not mods:
            continue
        starts = [s for s, _ in mods]
        by_scope: dict[str, list] = {}
        for op, s, d in lines.get("ops", []):
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            # an op belongs to the module whose interval holds its middle
            mid = (c[0] + c[1]) / 2
            j = bisect.bisect_right(starts, mid) - 1
            if j >= 0 and mid <= mods[j][1]:
                by_scope.setdefault(scopes.get(op, ""), []).append(c)
        chips[plane] = {
            "modules": len(mods),
            "scope": {sc: _length(_union(v)) for sc, v in by_scope.items()},
            "search_ops": _covered_by(by_scope, lambda sc: True),
            "scoped": _covered_by(by_scope, bool),
            "local": _covered_by(by_scope, lambda sc: sc != "merge"),
        }
    return {"dispatches": dispatches, "chips": chips}


def reduce(segments: list[dict], scopes: dict[str, str]) -> dict:
    """The segments' numbers summed per chip, times in seconds. Raises where
    a chip that ran the program holds another count of its module events
    than the program's dispatch spans, in any segment."""
    if not segments:
        raise ValueError("no traced segment")
    parts = [segment(events, scopes) for events in segments]
    names = sorted({plane for p in parts for plane in p["chips"]})
    chips = {plane: {"scope_s": {}, "search_ops_s": 0.0, "scoped_s": 0.0, "local_s": 0.0,
                     "modules": 0} for plane in names}
    for i, p in enumerate(parts):
        for plane in names:
            got = p["chips"].get(plane)
            modules = got["modules"] if got else 0
            if p["dispatches"] and modules != p["dispatches"]:
                raise RuntimeError(
                    f"traced segment {i}: {plane} holds {modules} {MODULE} events for "
                    f"{p['dispatches']} search dispatches: the profiler dropped its "
                    "device events; trace shorter units")
            if got is None:
                continue
            out = chips[plane]
            out["modules"] += modules
            for key in ("search_ops", "scoped", "local"):
                out[key + "_s"] += got[key] / 1e9
            for sc, t in got["scope"].items():
                out["scope_s"][sc] = out["scope_s"].get(sc, 0.0) + t / 1e9
    return {"dispatches": sum(p["dispatches"] for p in parts), "chips": chips}


def _covered(reduced: dict) -> bool:
    chips = reduced["chips"].values()
    return sum(c["scoped_s"] for c in chips) >= COVERED * sum(c["search_ops_s"] for c in chips)


def notes(reduced: dict) -> list[str]:
    """What a traced run prints about the sharded program, chip by chip."""
    chips = reduced["chips"]
    scoped = sum(c["scoped_s"] for c in chips.values())
    ops = sum(c["search_ops_s"] for c in chips.values())
    short = "" if _covered(reduced) else f"; under {COVERED:.0%}: the sharded readers report nothing"
    lines = [f"sharded search: {len(chips)} chips, scoped op time {scoped:.6f} s of {ops:.6f} s "
             f"({100 * scoped / ops if ops else 0:.3f}%{short}); search dispatches "
             f"{reduced['dispatches']}"]
    for plane, c in chips.items():
        lines.append(
            f"{plane}: {c['modules']} {MODULE} events, op time {c['search_ops_s']:.6f} s, "
            f"outside merge {c['local_s']:.6f} s: "
            + ", ".join(f"{sc or 'no scope'} {t:.6f}" for sc, t in sorted(c["scope_s"].items())))
    return lines


@functools.lru_cache(maxsize=1)
def _load(files: tuple[tuple[str, int], ...], rows: int, dim: int, ks: tuple[int, ...]) -> dict:
    segments = [extract(pathlib.Path(f)) for f, _ in files]
    n_chips = len({plane for events in segments for plane, lines in events["devices"].items()
                   if any(n.split("(")[0] == MODULE for n, _, _ in lines.get("modules", []))})
    if not ks or not n_chips:
        return {"dispatches": 0, "chips": {}}
    reduced = reduce(segments, sharded_scopes(rows, dim, ks, n_chips))
    for line in notes(reduced):
        print(line, file=sys.stderr, flush=True)
    return reduced


def read(run, trace_dir: pathlib.Path | None = None) -> dict | None:
    """The per-chip reduction of the run's traced segments (``None``
    untraced): the last session under each unit directory of the trace
    directory, as ``program_trace.read`` finds them."""
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
    return _load(tuple(files), run.rows, run.dim, ks)


def _chips(run) -> dict | None:
    """The chips of a traced run that ran the sharded program, where the
    scope map covers their op time; ``None`` otherwise."""
    reduced = read(run)
    if reduced is None or not reduced["chips"] or not run.counters.searches:
        return None
    return reduced["chips"] if _covered(reduced) else None


def scope_ms_per_query(run, scope: str) -> float | None:
    """Device time of the sharded program's ops in ``scope``, the mean over
    the chips, ms per query searched."""
    chips = _chips(run)
    if chips is None:
        return None
    t = sum(c["scope_s"].get(scope, 0.0) for c in chips.values()) / len(chips)
    return t * 1e3 / _searched(run) if t > 0 else None


def shard_skew_pct(run) -> float | None:
    """The busiest chip's op time outside ``merge`` over the chips' mean,
    minus 100, in percent."""
    chips = _chips(run)
    if chips is None or len(chips) < 2:
        return None
    local = [c["local_s"] for c in chips.values()]
    mean = sum(local) / len(local)
    return 100.0 * max(local) / mean - 100.0 if mean > 0 else None
