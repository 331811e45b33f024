"""Queries finalize re-executed over queries routed in the traced batches, percent (batch cells)."""

from chipbench.program_trace import replay_share_pct


def read(run):
    return replay_share_pct(run)
