"""A rehearsal of whole runs on the CPU at a tiny size (Pallas in interpret
mode), and the entry point's refusals."""
import json
import shutil
import subprocess
import sys
import time

import pytest

from conftest import DATA, ROOT, tiny_cell

E2E = {"setup_s", "out_tok_s", "ttft_p90_ms", "itl_p99_ms"}


def _run(harness_mod, peaks, cell, trace=False, seconds=3.0, seed=2**31 + 99):
    return harness_mod.run(cell, seed, seconds, trace, t_start=time.monotonic(), peaks=peaks,
                           log=lambda *a: None)


def test_open_loop_run_reports_every_end_to_end_metric(harness_mod, peaks):
    cell = tiny_cell()
    r = _run(harness_mod, peaks, cell)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True
    assert set(r["metrics"]) == E2E
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # the open loop offers exactly round(rate · seconds) requests in the window
    assert r["attempted"] == round(cell.spec["load"]["rate_per_s"] * 3.0)
    assert r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert r["checks"]["tokens_compared"]["value"] >= r["checks"]["tokens_compared"]["limit"]
    json.dumps(r)


def test_closed_loop_traced_run_reports_the_per_layer_metrics(harness_mod, peaks):
    r = _run(harness_mod, peaks, tiny_cell("closed"), trace=True)
    assert r["correct"] is True and 0 <= r["failed"] <= r["attempted"]
    assert {"scatter_ms", "prefill_ms", "decode_step_ms", "compiles_in_window", "mfu"} <= set(r["metrics"])
    # a split metric reads as the one it splits
    assert r["metrics"]["mfu.itl"] == r["metrics"]["mfu"]
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["device"]["window_s"] > 0 and "busy_s" in r["device"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_traffic(harness_mod):
    cell = tiny_cell()
    a = harness_mod.pool_for(cell, harness_mod.arrivals_for(cell, 3.0), 5)
    b = harness_mod.pool_for(cell, harness_mod.arrivals_for(cell, 3.0), 5)
    items = [(a.next(), b.next()) for _ in range(6)]
    assert all((x.prompt == y.prompt).all() and x.max_new_tokens == y.max_new_tokens for x, y in items)


def test_every_seed_gets_the_same_sizes_and_arrivals(harness_mod):
    cell = tiny_cell()
    sizes, dues = [], []
    for seed in (1, 2**31 + 5):
        arrivals = harness_mod.arrivals_for(cell, 3.0)
        pool = harness_mod.pool_for(cell, arrivals, seed)
        sizes.append([(len(i.prompt), i.max_new_tokens) for i in (pool.next() for _ in range(2 * pool.size))])
        arrivals.start(0.0)
        dues.append(arrivals.due(5.999))  # two periods of 3 s
    assert sizes[0] == sizes[1] and dues[0] == dues[1]
    assert len(dues[0]) == 2 * round(cell.spec["load"]["rate_per_s"] * 3.0)


def _lines(proc):
    return [ln for ln in proc.stdout.splitlines() if ln.strip()]


def test_run_py_refuses_a_cpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "ouro2.6b-2L.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any('"metrics"' in ln for ln in _lines(p))
    assert "needs a TPU" in p.stderr


def test_run_py_alone_is_not_a_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "ouro2.6b-2L.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any('"metrics"' in ln for ln in _lines(p))


@pytest.mark.parametrize("name,e2e", [
    ("ouro2.6b-2L.chat", E2E - {"out_tok_s"}),  # an open loop below the knee: its tails
    ("minicpm2b-2L.docqa", E2E),
])
def test_every_cell_finds_its_files(harness_mod, name, e2e):
    cell = harness_mod.load_cell(name)
    assert {m["name"] for m in cell.end_to_end} == e2e
    assert len(cell.per_layer) == 8
    assert all(m["moves"] in e2e for m in cell.per_layer)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.module("metrics", m["name"]).read)
    path = cell.path()
    assert callable(path.build) and callable(path.work) and path.KERNELS
    ref = cell.reference()
    assert callable(ref.make_weights) and callable(ref.checks)
    cell.module("arrivals", cell.traffic["arrival"])
    cell.module("faults", cell.config["path"])


#: A per-layer metric added as a file: live slots per decode step.
LIVE_SLOTS = '''"""Mean live slots per decode step in the window."""


def read(run):
    steps = [len(c[3]) for c in run.calls if c[0] == "decode"]
    return sum(steps) / len(steps) if steps else None
'''

#: A path added as a file: the token path, every engine step counted.
COUNTING_PATH = '''"""The token path with its engine steps counted."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location("counting_base", Path(__file__).with_name("token_path.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)
KERNELS = base.KERNELS
work = base.work
STEPS = []


class Served(base.Served):
    def step(self):
        STEPS.append(self.queue_len())
        super().step()


def build(cell, weights):
    return Served(cell, weights)
'''


def test_a_metric_and_a_path_are_new_files_alone(harness_mod, peaks, tmp_path):
    """A cell on a new path with a new per-layer metric runs from new files
    and new entries in BENCHMARK.json; no file the benchmark had changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/metrics/live_slots.py").write_text(LIVE_SLOTS)
    (tmp_path / "bench/paths/counting.py").write_text(COUNTING_PATH)
    config = json.loads((DATA / "tiny.config.json").read_text())
    config["path"] = "counting"
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(config))
    shutil.copy(DATA / "tiny.traffic.json", tmp_path / "bench/traffic/tiny.json")
    shutil.copy(DATA / "tiny.cell.json", tmp_path / "bench/cells/tiny.counting.json")
    bench["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.counting", "config": "tiny", "traffic": "tiny", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "live_slots", "unit": "slots", "better": "higher",
                               "source": "program_span", "layer": "serving loop", "moves": "out_tok_s",
                               "workloads": ["tiny.counting"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness_mod.load_cell("tiny.counting", root=tmp_path)
    r = harness_mod.run(cell, 2**31 + 21, 3.0, True, t_start=time.monotonic(), peaks=peaks,
                        log=lambda *a: None)
    assert r["correct"] is True
    assert 1 <= r["metrics"]["live_slots"]["value"] <= cell.spec["engine"]["slots"]
    assert cell.module("paths", "counting").STEPS
    assert all(p.read_bytes() == b for p, b in before.items())
