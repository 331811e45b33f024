"""Time in the engine's embedder, ms per query embedded (batch cells)."""

from chipbench.readers import self_ms_per_query


def read(run):
    return self_ms_per_query(run, ("embed",), "embedded")
