"""Mean number of distinct experts with at least one routed row, per layer
and decode step: the program's counters ``tokenpath.moe.decode.experts_hit``
over ``tokenpath.moe.decode.layer_calls`` (``repro.obs``), since the
warm-up.  A path without them reads nothing."""


def read(run):
    calls = run.counters.get("tokenpath.moe.decode.layer_calls", 0)
    return run.counters["tokenpath.moe.decode.experts_hit"] / calls if calls else None
