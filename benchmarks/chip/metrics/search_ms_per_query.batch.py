"""Device time of the search programs, ms per query searched (batch cells)."""

from chipbench.readers import search_ms_per_query


def read(run):
    return search_ms_per_query(run)
