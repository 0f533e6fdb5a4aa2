"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny width in
interpret mode, and its refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.serving.token_path import TokenPathConfig  # noqa: E402


class TestPhases:
    def test_token_path_phase_interpret(self):
        out = chip_smoke.phase_a(
            TokenPathConfig(), backend="interpret", slots=2, max_len=64,
            prompt_lens=(5, 30), new_tokens=3,
        )
        assert out["prefills"] == 2 and out["decode_steps"] == 2
        assert out["generated_tokens"] == 6

    def test_one_shot_phase_interpret(self):
        out = chip_smoke.phase_b(backend="interpret", waves=(3, 1))
        assert out["requests"] == 4 and out["cells"] == [1, 4]

    def test_mismatch_is_reported(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="1 of 4 elements differ"):
            chip_smoke.require_equal("x", [1, 2, 3, 4], [1, 2, 3, 5])


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_fails_without_tpu_or_repo(where, tmp_path):
    """No TPU, or no repository beside the script: non-zero exit, no result."""
    cwd = REPO
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    proc = _run_script(cwd)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
