"""E7: serving-engine next-token selection — greedy vs temperature/top-k —
plus sampling determinism (the module fallback rng).
"""
import numpy as np

from repro.serving.engine import EngineConfig, ServeEngine, sample_token, seed_sampler


def _logits(rng, vocab=32):
    return rng.normal(size=(vocab,)).astype(np.float32) * 3.0


class TestSampleToken:
    def test_zero_temperature_is_argmax(self):
        rng = np.random.default_rng(0)
        for _ in range(10):
            z = _logits(rng)
            assert sample_token(z, temperature=0.0) == int(z.argmax())

    def test_top_k_one_is_argmax(self):
        rng = np.random.default_rng(1)
        srng = np.random.default_rng(2)
        for _ in range(10):
            z = _logits(rng)
            assert sample_token(z, temperature=1.0, top_k=1, rng=srng) == int(z.argmax())

    def test_top_k_restricts_support(self):
        rng = np.random.default_rng(3)
        z = _logits(rng)
        k = 5
        allowed = set(np.argsort(z)[-k:].tolist())
        srng = np.random.default_rng(4)
        drawn = {sample_token(z, temperature=2.0, top_k=k, rng=srng) for _ in range(300)}
        assert drawn <= allowed
        assert len(drawn) > 1  # it actually samples, not argmax

    def test_low_temperature_concentrates(self):
        rng = np.random.default_rng(5)
        z = _logits(rng)
        srng = np.random.default_rng(6)
        hot = [sample_token(z, temperature=0.01, rng=srng) for _ in range(100)]
        assert np.mean(np.asarray(hot) == z.argmax()) > 0.95

    def test_seeded_rng_is_deterministic(self):
        rng = np.random.default_rng(7)
        z = _logits(rng)
        a = [sample_token(z, temperature=1.0, top_k=4, rng=np.random.default_rng(42)) for _ in range(20)]
        b = [sample_token(z, temperature=1.0, top_k=4, rng=np.random.default_rng(42)) for _ in range(20)]
        assert a == b

    def test_no_rng_uses_seeded_module_stream(self):
        """Without an explicit rng the draws come from one seeded module
        stream (not a fresh default_rng per call) — re-seeding replays the
        exact sequence."""
        z = _logits(np.random.default_rng(11))
        seed_sampler(123)
        a = [sample_token(z, temperature=1.5) for _ in range(20)]
        seed_sampler(123)
        b = [sample_token(z, temperature=1.5) for _ in range(20)]
        assert a == b
        # and it is a stream, not a constant: consecutive draws differ somewhere
        assert len(set(a)) > 1

    def test_module_stream_matches_equivalent_generator(self):
        """The fallback draws exactly as an explicitly-threaded generator
        with the same seed would — no hidden extra state."""
        z = _logits(np.random.default_rng(12))
        seed_sampler(7)
        a = [sample_token(z, temperature=1.0, top_k=6) for _ in range(10)]
        rng = np.random.default_rng(7)
        b = [sample_token(z, temperature=1.0, top_k=6, rng=rng) for _ in range(10)]
        assert a == b


class TestEngineSelect:
    def _engine(self, **cfg_kwargs):
        # _select only touches ecfg + _rng; skip the heavy model setup
        eng = object.__new__(ServeEngine)
        eng.ecfg = EngineConfig(**cfg_kwargs)
        eng._rng = np.random.default_rng(eng.ecfg.seed)
        return eng

    def test_greedy_branch(self):
        eng = self._engine(greedy=True)
        z = _logits(np.random.default_rng(8))
        assert eng._select(z) == int(z.argmax())

    def test_sampling_branch_is_not_dead(self):
        """Non-greedy must actually sample — over many draws from a flat-ish
        distribution it cannot always return argmax."""
        eng = self._engine(greedy=False, temperature=5.0, seed=0)
        z = _logits(np.random.default_rng(9))
        draws = {eng._select(z) for _ in range(200)}
        assert len(draws) > 1

    def test_sampling_respects_top_k(self):
        eng = self._engine(greedy=False, temperature=2.0, top_k=3, seed=1)
        z = _logits(np.random.default_rng(10))
        allowed = set(np.argsort(z)[-3:].tolist())
        assert {eng._select(z) for _ in range(200)} <= allowed
