"""``device_idle`` in the cells judged by their tails (open loop below the
knee), where the device's work moves ``itl_p99_ms``: the same reading as
``bench/metrics/device_idle.py``."""


def read(run):
    return run.cell.module("metrics", "device_idle").read(run)
