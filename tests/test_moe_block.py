"""The sparse-expert (Qwen2-MoE) block on the compiled token path, at a small
size on the CPU: hidden 64, 4 heads, 8 experts of 32 with top-2 routing, a
shared expert of 96, 2 layers, seeded random weights.

- compiled prefill then decode through the cache, on the ``ref`` and
  ``interpret`` backends, equal to the plain reference's full forward pass
  (``repro.serving.moe_reference``) and to the jnp mirrors, bit for bit;
- the grouped ``qmoe`` kernel against its oracle where experts get no rows,
  where every row goes to one expert, and with odd rows per expert;
- the fused plan step against the unfused semantic region;
- the adapter's device prefill against the host prefill: the same logits
  row, K/V rows, routing and routing counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compile import compile_model
from repro.core.runtime import ReferenceRuntime
from repro.kernels import pack
from repro.kernels import qmoe as qmoe_kernel
from repro.kernels import ref as kref
from repro.obs.metrics import default_registry
from repro.serving import moe_reference
from repro.serving.token_path import (
    CompiledTokenAdapter,
    CompiledTokenPath,
    TokenPathConfig,
    causal_mask,
    decode_jax,
    make_token_params,
    prefill_jax,
)

CFG = TokenPathConfig(
    vocab=96, d_model=64, n_heads=4, d_ff=96, n_layers=2, block="moe",
    n_experts=8, top_k=2, d_expert=32, max_pos=64,
)


@pytest.fixture(scope="module")
def params():
    return make_token_params(CFG, seed=3)


@pytest.fixture(scope="module", params=["ref", "interpret"])
def tp(request, params):
    return CompiledTokenPath(CFG, params, backend=request.param, s_granularity=16)


def test_every_layer_fuses_its_experts(tp):
    for cm in (tp.prefill_cm, tp.decode_cm):
        assert cm.stats["fused_qmoe"] == CFG.n_layers
        assert cm.stats["fused_norm"] == 2 * CFG.n_layers + 1
        assert cm.stats["fused_router"] == CFG.n_layers
        # per layer: qkv, o, shared gate, up, down and gate logit
        assert cm.stats["fused_qlinear"] == 6 * CFG.n_layers
        assert cm.stats["fused_qattention"] == CFG.n_layers * CFG.n_heads


def test_prefill_then_decode_equals_the_reference_and_the_mirrors(tp, params):
    """A prompt of 9 tokens prefilled, then 5 greedy tokens decoded through
    the cache: every logit row equals the reference's full forward pass over
    the tokens served, and the jnp mirrors bit for bit."""
    rng = np.random.default_rng(7)
    plen, steps, cache_len = 9, 5, 16
    prompt = rng.integers(1, CFG.vocab, (1, plen)).astype(np.int32)
    pos = np.arange(plen)[None].astype(np.int32)
    logits, rows = tp.prefill(prompt, causal_mask(1, plen), pos)
    want, _ = prefill_jax(CFG, params, prompt, causal_mask(1, plen), positions=pos)
    np.testing.assert_array_equal(logits, np.asarray(want))
    cache = {k: np.zeros((1, cache_len, CFG.d_model), np.int8) for k in rows}
    for k in rows:
        cache[k][:, :plen] = rows[k]
    mirror = [(cache[f"k_cache_{l}"], cache[f"v_cache_{l}"]) for l in range(CFG.n_layers)]
    served = list(prompt[0])
    rows_out = [logits[0]]
    tok = int(logits[0, -1].argmax())
    for step in range(steps):
        p = plen + step
        out, cache = tp.decode_step(np.array([[tok]], np.int32), np.array([p]), cache)
        onehot = np.zeros((1, cache_len, 1), np.int8)
        onehot[0, p, 0] = 1
        mask = (np.arange(cache_len)[None, None, :] <= p).astype(np.float32)
        want, mirror = decode_jax(CFG, params, np.array([[tok]], np.int32), onehot, mask, mirror,
                                  positions=np.array([[p]]))
        np.testing.assert_array_equal(out, np.asarray(want)[:, 0])
        served.append(tok)
        rows_out.append(out)
        tok = int(out[0].argmax())
    ref = np.asarray(moe_reference.forward(CFG, params, np.array(served, np.int32)))
    np.testing.assert_array_equal(np.concatenate(rows_out), ref)


ROUTING = ("tokenpath.moe.rows", "tokenpath.moe.experts_hit", "tokenpath.moe.layer_calls")


@pytest.mark.parametrize("bucket,plen", [(16, 1), (16, 15), (16, 16), (32, 1), (32, 31), (32, 32)])
def test_the_device_prefill_equals_the_host_prefill(tp, bucket, plen):
    """``CompiledTokenAdapter.prefill`` (one jitted step: the logits row at
    ``plen - 1`` and the routing to the host, the K/V rows left on the
    device) against ``CompiledTokenPath.prefill``, bit for bit; the routing
    is counted the same, and only the row and the routing are fetched."""
    reg = default_registry()
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = np.random.default_rng(40 + plen).integers(1, CFG.vocab, plen)

    def counted(fn):
        before = [reg.counter(n).value for n in ROUTING + ("tokenpath.prefill.fetched_bytes",)]
        out = fn()
        after = [reg.counter(n).value for n in ROUTING + ("tokenpath.prefill.fetched_bytes",)]
        return out, [b - a for a, b in zip(before, after)]

    (logits, cache), host_counts = counted(
        lambda: tp.prefill(padded, causal_mask(1, bucket), np.arange(bucket)[None]))
    host_routing = tp.last_routing
    (row, rows), counts = counted(lambda: CompiledTokenAdapter(tp).prefill(padded, plen, 64))
    np.testing.assert_array_equal(row, logits[0, plen - 1])
    for name, got in rows.items():
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), cache[name])
    assert isinstance(tp.last_routing, np.ndarray)
    assert tp.last_routing.shape == (CFG.n_layers, 1, bucket, CFG.top_k)
    np.testing.assert_array_equal(tp.last_routing, host_routing)
    assert counts[:3] == host_counts[:3]
    assert counts[3] == CFG.vocab * 4 + tp.last_routing.nbytes


def _expert_weights(e=8, d=64, f=96, seed=0):
    rng = np.random.default_rng(seed)
    wg = rng.integers(-30, 31, (e, d, f)).astype(np.int8)
    wu = rng.integers(-30, 31, (e, d, f)).astype(np.int8)
    wd = rng.integers(-8, 8, (e, f, d)).astype(np.int8)
    return wg, wu, wd


SCALES = dict(r_g=0.004, s_g=0.05, r_u=0.0041, r_h=2.0, r_d=0.02)


def _routing(case, rows, e=8, seed=1):
    """Chosen experts ``(rows, 2)`` for one edge case."""
    rng = np.random.default_rng(seed)
    if case == "zero_rows":  # experts 3..7 get no row
        idx = np.stack([rng.permutation(3)[:2] for _ in range(rows)])
    elif case == "one_expert":  # every row goes to expert 5
        idx = np.stack([[5, int(rng.choice([i for i in range(e) if i != 5]))] for _ in range(rows)])
    else:  # odd rows per expert: rows pair (2i, 2i+1) with i < 4, so 3, 5, ... rows each
        idx = np.stack([[2 * (r % 4), 2 * (r % 4) + 1] for r in range(rows)])
    return idx.astype(np.int32)


@pytest.mark.parametrize("case,rows", [("zero_rows", 11), ("one_expert", 40), ("odd_rows", 13)])
def test_qmoe_matches_its_oracle(case, rows):
    wg, wu, wd = _expert_weights()
    e, d, f = wg.shape
    rng = np.random.default_rng(2)
    x = rng.integers(-60, 61, (rows, d)).astype(np.int8)
    idx = _routing(case, rows)
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(rows, e)), jnp.float32), axis=-1)
    want = kref.qmoe_ref(jnp.asarray(x), jnp.asarray(idx), probs, jnp.asarray(wg), jnp.asarray(wu),
                         jnp.asarray(wd), **SCALES)

    def padded(w, a, b):
        out = np.zeros((e, a, b), np.int8)
        out[:, : w.shape[1], : w.shape[2]] = w
        return out

    wdp = np.stack([pack.pack_int4(w) for w in padded(wd, 128, 128)])
    got = qmoe_kernel.qmoe(jnp.asarray(x), jnp.asarray(idx), probs, jnp.asarray(padded(wg, 128, 128)),
                           jnp.asarray(padded(wu, 128, 128)), jnp.asarray(wdp), d=d, down_bits=4,
                           interpret=True, **SCALES)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(want)).max() > 0


@pytest.mark.parametrize("case", ["zero_rows", "one_expert", "odd_rows"])
def test_only_hit_experts_own_tiles(case):
    """The grouped layout gives tiles to exactly the experts with rows (so
    no other expert's weights are read), each expert's rows in whole tiles,
    and every assignment its own padded row."""
    idx = _routing(case, 13)
    bm = 32
    slot, tile_expert, tile_src, tiles = (np.asarray(a) for a in qmoe_kernel.group_layout(jnp.asarray(idx), 8, bm))
    counts = np.bincount(idx.reshape(-1), minlength=8)
    assert int(tiles) == int(np.sum(-(-counts // bm)))
    assert set(tile_expert[: int(tiles)].tolist()) == set(np.flatnonzero(counts).tolist())
    # tiles past the real ones read the last real tile's blocks
    assert (tile_src[int(tiles):] == int(tiles) - 1).all()
    assert (tile_expert[int(tiles):] == tile_expert[int(tiles) - 1]).all()
    assert len(set(slot.tolist())) == idx.size
    assert (tile_expert[slot // bm] == idx.reshape(-1)).all()


def test_the_fused_step_equals_the_unfused_region(params):
    """The plan with the ``qmoe`` step, the plan that runs the region's
    semantic form (every expert, zero weights where not chosen) through the
    generic ops, and the reference runtime agree bit for bit."""
    tp = CompiledTokenPath(CFG, params, backend="ref")
    rng = np.random.default_rng(5)
    n, s = 2, 12
    feeds = {
        "tokens": rng.integers(1, CFG.vocab, (n, s)).astype(np.int32),
        "mask": causal_mask(n, s),
        "positions": np.broadcast_to(np.arange(s), (n, s)).astype(np.int32).copy(),
    }
    fused = tp.prefill_cm.run(feeds)
    unfused = compile_model(tp.prefill_model, backend="ref", fuse=False)
    assert unfused.stats["fused_qmoe"] == unfused.stats["fused_norm"] == unfused.stats["fused_router"] == 0
    assert not {"qmoe", "rmsnorm", "softmax_rn"} & {st.kernel for st in unfused.plan.steps}
    plain = unfused.run(feeds)
    numpy_rt = ReferenceRuntime(tp.prefill_model).run(feeds)
    for name in fused:
        np.testing.assert_array_equal(fused[name], plain[name])
        np.testing.assert_array_equal(fused[name], numpy_rt[name])


@pytest.mark.parametrize("off", [0, 1, -1, 2, -2])
def test_the_norm_root_and_quotient_are_the_nearest_f32(off):
    """``sqrt_rn`` and ``div_signed`` (RMSNorm's root and quotient on every
    backend) return IEEE's result from a hardware result up to two ulps off,
    as a TPU's are, over the ranges a norm meets."""
    rng = np.random.default_rng(off + 11)
    a = (rng.integers(0, 2 * 127 * 127, 100_000) / np.float32(64.0) + np.float32(4e-4)).astype(np.float32)
    a[:1000] = rng.random(1000).astype(np.float32) * 1e-3
    root = np.sqrt(a)
    moved = jax.lax.bitcast_convert_type(jax.lax.bitcast_convert_type(jnp.asarray(root), jnp.int32) + off,
                                         jnp.float32)
    np.testing.assert_array_equal(np.asarray(kref._nearest_root(jnp.asarray(a), moved)), root)
    np.testing.assert_array_equal(np.asarray(kref.sqrt_rn(jnp.asarray(a))), root)
    x = rng.integers(-128, 128, a.size).astype(np.float32)
    quot = x / root
    qbits = jax.lax.bitcast_convert_type(jnp.asarray(np.abs(quot)), jnp.int32) + off
    q = jnp.where(jnp.asarray(x) == 0, 0.0, jax.lax.bitcast_convert_type(qbits, jnp.float32))
    fixed = kref._nearest(jnp.abs(jnp.asarray(x)), jnp.asarray(root), kref._nearest(jnp.abs(jnp.asarray(x)), jnp.asarray(root), q))
    np.testing.assert_array_equal(np.asarray(fixed)[x != 0], np.abs(quot)[x != 0])
    np.testing.assert_array_equal(np.asarray(kref.div_signed(jnp.asarray(x), jnp.asarray(root))), quot)
