"""What the metric readers share (``metrics/<name>.py`` each call one).

A reader returns ``None`` where its run has nothing for it to read; the
harness then leaves the metric out of the result line. A reader never
returns 0 for a share of a roofline or of a peak.
"""

from __future__ import annotations

from chipbench.peaks import search_least_seconds


def answered_per_s(run) -> float:
    """Queries answered over every second of the window."""
    return (run.window.attempted - run.window.failed) / run.window.seconds


def self_ms_per_query(run, spans: tuple[str, ...], per: str) -> float | None:
    """Self time of the named spans, ms, over the queries routed or embedded."""
    if run.trace is None:
        return None
    n = run.counters.routed if per == "routed" else run.counters.embedded
    if n == 0:
        return None
    return sum(run.trace["self_s"].get(s, 0.0) for s in spans) * 1e3 / n


def _searched(run) -> int:
    return sum(nq for nq, _ in run.counters.searches)


def _search_device_s(run) -> float | None:
    """Device seconds of the search programs; loud where searches were
    served and the trace attributes no device time to them."""
    if run.trace is None or not run.counters.searches:
        return None
    t = run.trace["search_device_s"]
    if t <= 0:
        raise RuntimeError(
            f"{len(run.counters.searches)} dense searches were served in the traced "
            "window but the trace attributes no device time to the search programs"
        )
    return t


def search_ms_per_query(run) -> float | None:
    t = _search_device_s(run)
    return None if t is None else t * 1e3 / _searched(run)


def search_roofline_pct(run) -> float | None:
    """Least time of the searches as served (shapes and calls) over their
    device time, in percent."""
    t = _search_device_s(run)
    if t is None:
        return None
    least = sum(search_least_seconds(nq, k, run.rows, run.dim, run.peaks)
                for nq, k in run.counters.searches)
    return 100.0 * least / t


def device_idle_pct(run) -> float | None:
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
