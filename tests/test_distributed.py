"""Distributed behaviour on 8 forced host devices (subprocess-isolated so the
rest of the suite keeps a single device).

Covers: logical sharding rules + divisibility fallback, sharded train step on
a (2,2)=(data,model) mesh matching single-device numerics, int8 gradient
compression over a 'pod' axis (error feedback convergence), and elastic
checkpoint restore onto a different mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# heavyweight model/serving tier — excluded from the fast CI tier (scripts/check.sh)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


class TestShardingRules:
    def test_divisibility_fallback(self):
        out = run_py("""
            import jax, json
            from repro.distributed.sharding import spec_for, use_mesh
            mesh = jax.make_mesh((2, 4), ("data", "model"))
            with use_mesh(mesh):
                ok = spec_for((16, 32), ("embed", "heads"))      # both divide
                fb = spec_for((16, 6), ("embed", "heads"))       # 6 % 4 != 0 -> fallback
                b  = spec_for((8, 128), ("batch", None))
            print(json.dumps({"ok": str(ok), "fb": str(fb), "b": str(b)}))
        """)
        d = json.loads(out.strip().splitlines()[-1])
        assert "data" in d["ok"] and "model" in d["ok"]
        assert "model" not in d["fb"]
        assert "data" in d["b"]

    def test_multipod_batch_spans_pod_and_data(self):
        out = run_py("""
            import jax, json
            from repro.distributed.sharding import spec_for, use_mesh
            mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
            with use_mesh(mesh):
                s = spec_for((8, 64), ("batch", None))
            print(str(s))
        """)
        assert "pod" in out and "data" in out


class TestShardedTrainStep:
    def test_matches_single_device(self):
        """One train step on a (2,2) mesh == same step on 1 device (f32)."""
        code = """
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.configs.base import ShapeConfig
            from repro.distributed.sharding import use_mesh
            from repro.launch import steps as S, specs as SP
            from repro.launch.mesh import make_test_mesh
            from repro.models import model as M
            from repro.optim import adamw
            from repro.data.pipeline import Pipeline, DataConfig

            cfg = get_config("qwen3_1_7b", reduced=True)
            sc = ShapeConfig("t", "train", 32, 4, microbatches=2)
            step = S.make_train_step(cfg, sc, compute_dtype=jnp.float32, q_chunk=16, kv_chunk=16)
            batch = {k: jnp.asarray(v) for k, v in Pipeline(cfg, DataConfig(0)).batch(0, 4, 32).items()}
            params = M.init_params(jax.random.PRNGKey(0), cfg)
            opt = adamw.init(params)

            p1, o1, m1 = jax.jit(step)(params, opt, batch)   # single device

            mesh = make_test_mesh((2, 2), ("data", "model"))
            with use_mesh(mesh):
                p_sh = SP.params_shardings(jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)), mesh)
                o_sh = {"m": p_sh, "v": p_sh, "step": None}
                b_sh = SP.batch_shardings({k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}, mesh)
                params_d = jax.device_put(params, p_sh)
                opt_d = jax.device_put(opt, {"m": p_sh, "v": p_sh, "step": None}["m"] if False else jax.tree.map(lambda s: s, {"m": p_sh, "v": p_sh, "step": None}))
                opt_d = {"m": jax.device_put(opt["m"], p_sh), "v": jax.device_put(opt["v"], p_sh), "step": opt["step"]}
                batch_d = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
                p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, {"m": p_sh, "v": p_sh, "step": None}, b_sh))(params_d, opt_d, batch_d)

            print("LOSS", float(m1["loss"]), float(m2["loss"]))
            d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
            mx = max(jax.tree.leaves(d))
            print("MAXDIFF", mx)
            assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
            assert mx < 2e-4, mx
        """
        out = run_py(code)
        assert "MAXDIFF" in out


class TestGradCompression:
    def test_int8_allreduce_error_feedback(self):
        """Compressed cross-pod mean ≈ true mean; error feedback drives the
        accumulated bias to ~0 over repeated steps on a persistent gradient."""
        code = """
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import make_test_mesh
            from repro.optim.grad_compress import compressed_cross_pod_mean, init_residuals

            mesh = make_test_mesh((8,), ("pod",))
            g_global = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32))
            true_mean = g_global.mean(axis=0)

            @jax.jit
            def one_round(g, res):
                def body(g_l, r_l):
                    gm, r2 = compressed_cross_pod_mean({"w": g_l[0]}, {"w": r_l[0]}, axis="pod")
                    return gm["w"][None], r2["w"][None]
                return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("pod"), P("pod")),
                                 out_specs=(P("pod"), P("pod")))(g, res)

            res = jnp.zeros((8, 64), jnp.float32)
            total_true = jnp.zeros((64,))
            total_comp = jnp.zeros((64,))
            for step in range(50):
                gm, res = one_round(g_global, res)
                total_comp = total_comp + gm[0]
                total_true = total_true + true_mean
            one_err = float(jnp.abs(gm[0] - true_mean).max() / jnp.abs(true_mean).max())
            cum_err = float(jnp.abs(total_comp - total_true).max() / jnp.abs(total_true).max())
            print("ONE", one_err, "CUM", cum_err)
            assert one_err < 0.05            # single round: int8-accurate
            assert cum_err < 0.005           # error feedback kills the bias
        """
        out = run_py(code)
        assert "CUM" in out


class TestElasticRestore:
    def test_restore_onto_different_mesh(self, tmp_path):
        code = f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.checkpoint import ckpt
            from repro.distributed.sharding import use_mesh, sharding_for
            tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
            mesh_a = jax.make_mesh((4, 2), ("data", "model"))
            with use_mesh(mesh_a):
                sh_a = {{"w": sharding_for((8, 8), ("embed", "mlp"), mesh_a)}}
                tree_a = jax.device_put(tree, sh_a)
                ckpt.save({str(tmp_path)!r}, 3, tree_a)
            # restore onto a DIFFERENT mesh shape (elastic restart: 8 -> 4 devices)
            mesh_b = jax.make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
            with use_mesh(mesh_b):
                sh_b = {{"w": sharding_for((8, 8), ("embed", "mlp"), mesh_b)}}
                restored, step, _ = ckpt.restore({str(tmp_path)!r}, tree, shardings=sh_b)
            assert step == 3
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
            assert restored["w"].sharding.mesh.devices.size == 4
            print("ELASTIC_OK")
        """
        out = run_py(code)
        assert "ELASTIC_OK" in out
