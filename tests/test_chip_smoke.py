"""chip_smoke.py: its phases hold at the smoke config on the CPU, and the
script itself refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys

import pytest

from repro.configs import base

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_one_chip_phases_pass_at_smoke_size(chip_smoke):
    """Phases (b)-(f) with the smoke model, padded canvases, a megatick
    and the in-process HTTP server: the same comparisons the chip run
    makes, at a size the CPU runs in seconds."""
    cfg = base.get_config("llada-8b", smoke=True)
    sizes = chip_smoke.Sizes(slots=2, requests=4, prompt_len=8, gen_len=16,
                             block_len=8, steps=4, max_seq_len=32,
                             head_rows=16, megatick_k=4)
    smoke = chip_smoke.Smoke(cfg, sizes, 0, chip_smoke.CompileClock())
    smoke.one_chip()
    assert smoke.failures == []
    assert sorted(smoke.tokens) == [1, 2, 3, 4]


def test_refuses_a_machine_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs 1 TPU chip" in out.stderr
