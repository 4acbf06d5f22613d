"""Whole runs of the harness on the CPU at a tiny size: the look for a chip,
the check that decides ``correct``, its control, and faults planted in the
timed path underneath."""
import json
import os
import shutil
import subprocess
import sys


HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

import control  # noqa: E402
import run  # noqa: E402

SEED = 2 ** 31 + 12345       # beyond 32 signed bits


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "llada8b-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_a_run_without_a_tpu_exits_nonzero():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "needs 1 TPU chip" in p.stderr
    assert not p.stdout.strip()


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d)
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()


def _run(capsys, cell="tiny-open", trace=False):
    rc = run.run_cell(cell, SEED, 3.0, trace,
                      bench_path=os.path.join(DATA, "BENCHMARK.json"),
                      root=DATA, need_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_control_fails_where_the_program_passes(capsys):
    """The check's control on three seeds: the reference at float8 with
    MXFP4 sampling reads above the limits that sound runs stay under."""
    limits = json.load(open(os.path.join(DATA, "configs", "tiny.json")))[
        "check"]["limits"]
    control.main(["--workload", "tiny-open", "--seconds", "3",
                  "--seeds", "1", "2", str(SEED),
                  "--benchmark", os.path.join(DATA, "BENCHMARK.json"),
                  "--root", DATA])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith('{"seed"')]
    assert len(rows) == 3
    for r in rows:
        assert r["program"]["stream_mismatch"] == 0
        assert all(r["program"][k] <= lim for k, lim in limits.items()), r
        assert any(r["control"][k] > lim for k, lim in limits.items()), r


def test_a_sound_traced_run_is_correct(capsys):
    """The traced run of the closed tiny cell: correct, with the per-layer
    metrics that counters feed (the CPU has no device trace to read)."""
    result = _run(capsys, "tiny-closed", trace=True)
    assert result["correct"] is True, result["check"]
    assert 0 < result["metrics"]["slot_occupancy"]["value"] <= 100
    assert list(result)[-1] == "check"
