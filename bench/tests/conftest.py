"""Shared set-up of the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU: the tiny configuration in ``data/`` serves through the
same harness with the Pallas kernels in interpret mode.
"""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def tiny_cell(arrival: str = "poisson"):
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((DATA / "tiny.traffic.json").read_text())
    spec = json.loads((DATA / "tiny.cell.json").read_text())
    if arrival == "closed":
        traffic["arrival"] = "closed"
        spec["load"] = {"clients": 4, "think_s": 0.0}
        spec["pool"] = 16
    return harness.Cell("tiny", 1, json.loads((DATA / "tiny.config.json").read_text()),
                        traffic, spec, bench["end_to_end"], bench["per_layer"])


@pytest.fixture(scope="session")
def harness_mod():
    import jax

    import harness

    jax.monitoring.register_event_duration_secs_listener(harness.COMPILES)
    return harness


@pytest.fixture(scope="session")
def peaks():
    import work

    return work.load_peaks("TPU v5 lite")
