"""Compile each cell's largest programs (its path's ``rehearsal_programs``:
for the token path, the largest prefill bucket and the decode step) for a
described TPU v5e, with no chip attached, and print what the compiler says.

    JAX_PLATFORMS=cpu python bench/rehearse_compile.py [cell ...]

The TPU compiler ships with the installed ``libtpu``; it refuses here what
the chip would refuse (a tile that overflows VMEM, a program that does not
fit), at no chip time.  Each line gives the program's ``memory_analysis()``
and its count of Pallas calls.  Nothing runs, so nothing is timed.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402


def _compile(plan, feeds, on):
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on)  # noqa: E731
    params = jax.tree.map(spec, plan.params())
    feeds = {k: jax.ShapeDtypeStruct(s, d, sharding=on) for k, (s, d) in feeds.items()}
    return jax.jit(plan.execute).lower(feeds, params).compile()


def rehearse(name: str, on) -> None:
    cell = harness.load_cell(name)
    t = time.monotonic()
    weights = cell.reference().make_weights(cell.config, 0)
    programs = cell.path().rehearsal_programs(cell, weights)
    print(f"{name}: built in {time.monotonic() - t:.1f} s; {', '.join(programs)}", flush=True)
    for what, (plan, feeds) in programs.items():
        t = time.monotonic()
        compiled = _compile(plan, feeds, on)
        calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"  {what}: compiled in {time.monotonic() - t:.1f} s, {calls} Pallas calls; "
              f"{compiled.memory_analysis()}", flush=True)


def main(argv) -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    on = SingleDeviceSharding(topo.devices[0])
    import json

    names = argv or [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        rehearse(name, on)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
