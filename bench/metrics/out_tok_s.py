"""Output tokens stamped inside the window, over the window's seconds."""
import stats


def read(run):
    return stats.tokens(run.window) / run.seconds
