"""Least time of the searches as served over their device time, percent (batch cells)."""

from chipbench.readers import search_roofline_pct


def read(run):
    return search_roofline_pct(run)
