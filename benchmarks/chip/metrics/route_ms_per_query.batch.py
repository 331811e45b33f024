"""Self time of the route stage (embedding excluded), ms per routed query (batch cells)."""

from chipbench.readers import self_ms_per_query


def read(run):
    return self_ms_per_query(run, ("route",), "routed")
