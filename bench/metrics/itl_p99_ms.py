"""Nearest-rank p99 over every gap between consecutive output tokens of a
request whose later token falls inside the window."""
import stats


def read(run):
    gaps = stats.token_gaps(run.window)
    return 1e3 * stats.percentile(gaps, 99) if gaps else None
