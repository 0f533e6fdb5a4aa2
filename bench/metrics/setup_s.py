"""Set-up time: from the first line of ``bench/run.py`` to the first timed
request (weights from the seed, the served path built, every shape the
cell's traffic reaches run once)."""


def read(run):
    return run.setup_s
