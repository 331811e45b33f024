"""Device time of the sharded search program's ``score`` scope, the mean over
the chips, ms per query searched (row-sharded cells)."""

from chipbench.sharded_trace import scope_ms_per_query


def read(run):
    return scope_ms_per_query(run, "score")
