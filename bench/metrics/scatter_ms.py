"""Mean wall time of one admission's ``adapter.scatter`` (the prompt's K/V
rows written into the slot's cache), from the benchmark's span around the
call, which ends when its results are ready."""


def read(run):
    spans = run.spans("scatter")
    return 1e3 * sum(spans) / len(spans) if spans else None
