"""Device time of the search programs' ``score`` scope, ms per query searched (batch cells)."""

from chipbench.program_trace import scope_ms_per_query


def read(run):
    return scope_ms_per_query(run, "score")
