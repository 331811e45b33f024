"""The busiest chip's op time of the sharded search outside ``merge`` over the
chips' mean, minus 100, percent (row-sharded cells)."""

from chipbench.sharded_trace import shard_skew_pct


def read(run):
    return shard_skew_pct(run)
