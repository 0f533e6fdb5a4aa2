"""Token-engine request lifecycle: admission validation and the generation
budget.

The ``max_new_tokens`` contract: a request gets back *exactly* that many
tokens — the prefill emits the first one, so a budget of 1 completes at
admission without ever occupying a decode slot, and a budget of n decodes
exactly n-1 more.  Admission rejects what the per-slot KV cache cannot hold
instead of silently clipping the cache write mid-decode.
"""
from collections import deque

import numpy as np
import pytest

from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.token_path import CompiledTokenAdapter, CompiledTokenPath, TokenPathConfig


def _serve(*, requests, prompt_len, new_tokens, slots, seed=0):
    """``requests`` random prompts of ``prompt_len`` tokens, ``new_tokens``
    each, served to the end through the compiled token path (``ref``
    backend); returns the requests and the drained engine."""
    tp = CompiledTokenPath(TokenPathConfig(), backend="ref", seed=seed, s_granularity=8)
    ecfg = EngineConfig(slots=slots, max_len=prompt_len + new_tokens + 8, seed=seed)
    eng = ServeEngine(ecfg=ecfg, adapter=CompiledTokenAdapter(tp))
    rng = np.random.default_rng(seed)
    reqs = [
        Request(uid=i, prompt=rng.integers(1, tp.cfg.vocab, (prompt_len,)).astype(np.int32),
                max_new_tokens=new_tokens)
        for i in range(requests)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return reqs, eng


def _engine_shell(**cfg_kwargs):
    """An engine with only the admission surface wired up — ``submit`` needs
    just the config and the queue (same idiom as test_serving_sampling)."""
    eng = object.__new__(ServeEngine)
    eng.ecfg = EngineConfig(**cfg_kwargs)
    eng.queue = deque()
    return eng


class TestSubmitValidation:
    def test_overlong_prompt_rejected(self):
        eng = _engine_shell(max_len=64, prefill_bucket=32)
        with pytest.raises(ValueError, match="KV cache"):
            eng.submit(Request(uid=0, prompt=np.arange(65, dtype=np.int32)))
        # bucket overflow, not just raw length: 50 tokens pad to bucket 64,
        # which fits — but decoding past max_len would clip, so only a
        # single-token budget is admissible at plen >= max_len
        eng.submit(Request(uid=1, prompt=np.arange(50, dtype=np.int32), max_new_tokens=8))
        assert len(eng.queue) == 1

    def test_prompt_at_max_len_admits_only_single_token_budget(self):
        eng = _engine_shell(max_len=64, prefill_bucket=32)
        eng.submit(Request(uid=0, prompt=np.arange(64, dtype=np.int32), max_new_tokens=1))
        with pytest.raises(ValueError, match="KV cache"):
            eng.submit(Request(uid=1, prompt=np.arange(64, dtype=np.int32), max_new_tokens=2))
        assert len(eng.queue) == 1

    def test_empty_prompt_rejected(self):
        eng = _engine_shell()
        with pytest.raises(ValueError, match="at least one token"):
            eng.submit(Request(uid=0, prompt=np.zeros((0,), np.int32)))

    def test_nonpositive_budget_rejected(self):
        eng = _engine_shell()
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32), max_new_tokens=0))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32), max_new_tokens=-3))
        assert not eng.queue

    def test_rejected_requests_leave_no_state(self):
        eng = _engine_shell(max_len=32, prefill_bucket=32)
        bad = Request(uid=0, prompt=np.arange(40, dtype=np.int32))
        with pytest.raises(ValueError):
            eng.submit(bad)
        assert not eng.queue and bad.generated is None


@pytest.mark.slow
class TestGenerationBudget:
    @pytest.mark.parametrize("budget", [1, 2, 16])
    def test_exactly_max_new_tokens_generated(self, budget):
        """The off-by-one regression: the prefill token counts against the
        budget, so len(generated) == max_new_tokens exactly — including the
        budget-1 case, which must complete at admit without a decode."""
        reqs, eng = _serve(requests=5, prompt_len=12, new_tokens=budget, slots=2)
        assert all(r.done for r in reqs)
        assert [len(r.generated) for r in reqs] == [budget] * 5
        assert eng.metrics["completed"] == 5
        if budget == 1:
            # all five completed at admit: the decode loop never ran a slot
            # for them, so no decode step was needed at all
            assert eng.metrics["decode_steps"] == 0
        assert all(r.t_done is not None and r.t_done >= r.t_first for r in reqs)
