"""Search dispatches, each one read of the whole index, over queries routed
in the traced batches (batch cells, one chip or row-sharded)."""

from chipbench.index_reads import reads_per_query


def read(run):
    return reads_per_query(run)
