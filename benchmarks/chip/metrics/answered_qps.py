"""Queries answered per second over the whole closed-loop window."""

from chipbench.readers import answered_per_s


def read(run):
    return answered_per_s(run)
