"""Compile the Pallas kernels of the main path for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed beside JAX, compiles for
a chip that is described and not attached, and refuses what Mosaic cannot
lower.  Shapes are those of ``chip_smoke.py``'s phase A (the token path's
block at Ouro-2.6B widths: hidden 2048, FFN 5632, head dim 128, 4 decode
slots, cache length 1024).
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import qact_lut as qact
from repro.kernels import qattention as qatt
from repro.kernels import qmatmul as qmm
from repro.serving.token_path import CompiledTokenPath, TokenPathConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, FF, DH, T = 2048, 5632, 128, 1024


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off (its
    entries could not be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [32, 128])
@pytest.mark.parametrize("bits,k,n", [(8, D, FF), (4, FF, D)], ids=["w8_up", "w4_down"])
def test_qmatmul(spec, bits, k, n, m):
    bm, bk, bn = qmm.choose_tiles(m, k, n)
    if bits == 8:
        fn, w = qmm.qmatmul, spec((k, n), jnp.int8)
    else:
        fn, w = qmm.qmatmul_packed, spec((k // 2, n), jnp.uint8)
    text = _compiled_text(
        lambda x, w, b, s, h: fn(x, w, b, s, h, bm=bm, bk=bk, bn=bn),
        spec((m, k), jnp.int8), w, spec((1, n), jnp.int32),
        spec((1, n), jnp.float32), spec((1, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,s", [(4, 1), (1, 512)], ids=["decode", "prefill"])
def test_qattention(spec, b, s):
    text = _compiled_text(
        lambda q, k, v, mask, lut: qatt.qattention(
            q, k, v, mask, lut, qk_scale=0.01, big=30000.0, lut_scale=0.125,
            p_scale=127.0, rescale=1 / 127.0,
        ),
        spec((b, s, DH), jnp.int8), spec((b, T, DH), jnp.int8), spec((b, T, DH), jnp.int8),
        spec((b, s, T), jnp.float32), spec((256,), jnp.uint8),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lut_dtype", [jnp.int8, jnp.uint8])
def test_qact_lut(spec, lut_dtype):
    bm, bn = qact.choose_blocks(32, FF)
    text = _compiled_text(
        lambda x, lut: qact.qact_lut(x, lut, bm=bm, bn=bn),
        spec((32, FF), jnp.int8), spec((256,), lut_dtype),
    )
    assert "tpu_custom_call" in text


def test_decode_step_custom_calls(topo):
    """chip_smoke's count of Pallas kernels in the lowered decode step, on a
    small token path: one per fused matmul and one per head."""
    sys.path.insert(0, REPO)
    import chip_smoke

    cfg = TokenPathConfig()
    tp = CompiledTokenPath(cfg, backend="pallas")
    calls = chip_smoke.decode_custom_calls(tp, 4, 64, device=topo.devices[0])
    assert calls == chip_smoke.expected_custom_calls(cfg) == 12
