"""Median gap between consecutive step completions in the window, in ms:
the statistic that does not see a stall, beside the end-to-end rate that
does."""

import statistics


def read(run):
    gaps = run["facts"]["step_gaps_s"]
    if run["peaks"] is None or len(gaps) < 2:
        return None
    return 1000.0 * statistics.median(gaps[1:])
