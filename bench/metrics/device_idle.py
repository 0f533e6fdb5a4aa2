"""Share of the traced window in which no operation runs on the device:
1 - (union of the device operations' intervals) / window."""


def read(run):
    if run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
