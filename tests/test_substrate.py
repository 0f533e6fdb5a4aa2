"""Substrate tests: optimizer, schedules, data pipeline, checkpoint/restart,
fault tolerance, serving engine, end-to-end training loss decrease."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, Pipeline
from repro.distributed.fault_tolerance import (
    CheckpointManager,
    CheckpointManagerConfig,
    StragglerMonitor,
    run_resilient,
)
from repro.launch import steps as steps_lib
from repro.models import model as M
from repro.optim import adamw, schedule

# heavyweight model/serving tier — excluded from the fast CI tier (scripts/check.sh)
pytestmark = pytest.mark.slow


class TestOptim:
    def test_adamw_converges_quadratic(self):
        params = {"w": jnp.array([3.0, -2.0])}
        opt = adamw.init(params)
        cfg = adamw.AdamWConfig(weight_decay=0.0, grad_clip_norm=None)
        for _ in range(300):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
            params, opt, _ = adamw.update(g, opt, params, jnp.asarray(0.05), cfg)
        assert float(jnp.abs(params["w"]).max()) < 0.05

    def test_grad_clip(self):
        params = {"w": jnp.zeros(3)}
        opt = adamw.init(params)
        g = {"w": jnp.full((3,), 1e6)}
        _, _, m = adamw.update(g, opt, params, jnp.asarray(1e-3), adamw.AdamWConfig(grad_clip_norm=1.0))
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    def test_schedules(self):
        wc = schedule.warmup_cosine(jnp.arange(0, 1000, 100), peak_lr=1.0, warmup_steps=100, total_steps=1000)
        assert float(wc[0]) == 0.0 and float(wc[1]) == 1.0
        assert float(wc[-1]) < 0.5
        w = schedule.wsd(jnp.asarray([0, 50, 100, 500, 900, 999]), peak_lr=1.0, warmup_steps=100, stable_steps=700, decay_steps=200)
        np.testing.assert_allclose(np.asarray(w[2:4]), [1.0, 1.0])  # stable phase
        assert float(w[-1]) < 0.2  # decay phase

    def test_wsd_stable_phase_flat_then_decays(self):
        vals = schedule.wsd(jnp.arange(100, 800, 50), peak_lr=2e-4, warmup_steps=100, stable_steps=600, decay_steps=100)
        assert np.allclose(np.asarray(vals[:-1]), 2e-4)


class TestData:
    def test_deterministic_and_sharded(self):
        cfg = get_config("qwen3_1_7b", reduced=True)
        p1 = Pipeline(cfg, DataConfig(seed=7, shard_index=0, shard_count=2))
        p2 = Pipeline(cfg, DataConfig(seed=7, shard_index=1, shard_count=2))
        a = p1.batch(3, 4, 16)
        b = p1.batch(3, 4, 16)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])  # pure fn of step
        assert not np.array_equal(a["tokens"], p2.batch(3, 4, 16)["tokens"])  # shards differ
        assert not np.array_equal(a["tokens"], p1.batch(4, 4, 16)["tokens"])  # steps differ

    def test_family_specific_fields(self):
        enc = Pipeline(get_config("seamless_m4t_large_v2", reduced=True)).batch(0, 2, 16)
        assert "src_embeds" in enc and enc["src_embeds"].shape == (2, 16, 256)
        vlm_cfg = get_config("pixtral_12b", reduced=True)
        vlm = Pipeline(vlm_cfg).batch(0, 2, 16)
        assert vlm["patch_embeds"].shape == (2, vlm_cfg.frontend_tokens, vlm_cfg.d_model)
        assert vlm["tokens"].shape == (2, 16 - vlm_cfg.frontend_tokens)

    def test_file_source(self, tmp_path):
        path = str(tmp_path / "toks.bin")
        np.arange(10_000, dtype=np.uint32).tofile(path)
        cfg = get_config("qwen3_1_7b", reduced=True)
        p = Pipeline(cfg, DataConfig(seed=0, path=path))
        b = p.batch(0, 2, 32)
        assert b["tokens"].shape == (2, 32)
        assert (b["tokens"] >= 0).all() and (b["tokens"] < cfg.vocab_size).all()


class TestCheckpoint:
    def _tree(self, k=0):
        return {"a": jnp.arange(6.0).reshape(2, 3) + k, "b": {"c": jnp.ones((4,), jnp.int32) * k}}

    def test_roundtrip(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(d, 5, self._tree(2), extra={"note": "x"})
        restored, step, extra = ckpt.restore(d, self._tree(0))
        assert step == 5 and extra == {"note": "x"}
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(self._tree(2)["a"]))

    def test_latest_pointer_and_gc(self, tmp_path):
        d = str(tmp_path)
        mgr = CheckpointManager(CheckpointManagerConfig(d, interval_steps=1, keep_last=2))
        for s in range(1, 5):
            mgr.maybe_save(s, self._tree(s))
        assert ckpt.latest_step(d) == 4
        assert sorted(p for p in os.listdir(d) if p.startswith("step_")) == ["step_3", "step_4"]

    def test_atomic_no_partial_on_failure(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(d, 1, self._tree(1))

        class Boom:
            def __array__(self):
                raise RuntimeError("disk died")

        with pytest.raises(RuntimeError):
            ckpt.save(d, 2, {"a": Boom()})
        assert ckpt.latest_step(d) == 1  # old checkpoint intact
        restored, step, _ = ckpt.restore(d, self._tree(0))
        assert step == 1

    def test_resilient_restart_loop(self, tmp_path):
        d = str(tmp_path)
        mgr = CheckpointManager(CheckpointManagerConfig(d, interval_steps=1))
        crashes = {"n": 0}

        def make_state():
            return {"x": jnp.zeros(())}

        def step_fn(state, step):
            if step == 3 and crashes["n"] == 0:
                crashes["n"] += 1
                raise RuntimeError("node failure")
            return {"x": state["x"] + 1}

        final = run_resilient(make_state, step_fn, manager=mgr, total_steps=6)
        assert crashes["n"] == 1
        assert float(final["x"]) == 6.0  # all 6 steps applied exactly once

    def test_straggler_monitor(self):
        import time

        mon = StragglerMonitor(threshold=5.0)
        for s in range(3):
            mon.start_step()
            time.sleep(0.01)
            mon.end_step(s)
        mon.start_step()
        time.sleep(0.2)
        m = mon.end_step(3)
        assert m["straggler"] == 1.0 and mon.slow_steps == [3]


class TestTrainLoop:
    def test_loss_decreases_and_resumes(self, tmp_path):
        from repro.launch.train import train

        d = str(tmp_path / "ck")
        params, opt, hist = train(
            "qwen3_1_7b", steps=8, batch=4, seq=32, ckpt_dir=d, ckpt_interval=4, log_every=100
        )
        assert hist[-1] < hist[0], hist
        # resume from checkpoint: continues at step 5 without blowing up
        params2, opt2, hist2 = train(
            "qwen3_1_7b", steps=10, batch=4, seq=32, ckpt_dir=d, ckpt_interval=100, log_every=100
        )
        assert len(hist2) == 5  # steps 5..9 only
        assert int(opt2["step"]) == 10

    def test_qat_trains(self):
        from repro.launch.train import train

        _, _, hist = train("minicpm_2b", steps=6, batch=4, seq=32, qat=True, log_every=100)
        assert np.isfinite(hist).all() and hist[-1] < hist[0]


class TestServeEngine:
    def test_continuous_batching_drains(self):
        from repro.serving.engine import EngineConfig, Request, ServeEngine
        from repro.serving.token_path import CompiledTokenAdapter, CompiledTokenPath, TokenPathConfig

        tp = CompiledTokenPath(TokenPathConfig(), backend="ref", s_granularity=8)
        eng = ServeEngine(ecfg=EngineConfig(slots=2, max_len=12 + 4 + 8), adapter=CompiledTokenAdapter(tp))
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, prompt=rng.integers(1, tp.cfg.vocab, (12,)).astype(np.int32), max_new_tokens=4)
                for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(r.done for r in reqs)
        assert all(len(r.generated) == 4 for r in reqs)
        assert eng.metrics["completed"] == 5
