"""Median gap between consecutive ticks of the window that no flush lies
between: one iteration of player, env and staging on the host. A statistic of
pieces, so per-layer only."""

import statistics


def read(run):
    gaps = run["host_step_gaps"]
    if len(gaps) < 10:
        return None
    return 1e3 * statistics.median(gaps)
