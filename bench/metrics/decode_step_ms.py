"""Mean wall time of one ``adapter.decode`` (one batched step over every
slot, the logits back on the host), from the benchmark's span around the
call, which ends when its results are ready."""


def read(run):
    spans = run.spans("decode")
    return 1e3 * sum(spans) / len(spans) if spans else None
