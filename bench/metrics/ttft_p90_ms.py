"""Nearest-rank p90, over every request due in the window, of its first
token minus its due time; a request with no first token counts as beyond
any value."""
import stats


def read(run):
    return 1e3 * stats.ttft_percentile(run.window, 90)
