"""Device-idle time under the program's ``repro.search`` spans, ms per query searched (batch cells)."""

from chipbench.program_trace import search_idle_ms_per_query


def read(run):
    return search_idle_ms_per_query(run)
