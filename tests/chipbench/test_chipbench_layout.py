"""A cell, configuration, traffic mix, per-layer metric, or a new
architecture's plain reference, is added as a new file and found by name,
with no other file edited."""
import importlib.util
import json
import math
import os
import shutil
from pathlib import Path

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
TOY = "toy_grid4"            # block-causal on an absolute grid of 4
MS = 1_000_000
DEV = "/device:TPU:0"


def _copy(tmp_path):
    """chipbench/ copied under ``tmp_path``, and the bytes of its files."""
    bench = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return bench, {p: p.read_bytes() for p in bench.rglob("*")
                   if p.is_file()}


def _load_run(bench):
    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_run", bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_files_are_found_by_name(tmp_path):
    bench, before = _copy(tmp_path)
    cfg = json.loads((bench / "configs" / "qwen2-0.5b.json").read_text())
    cfg["name"] = "qwen2-0.5b-wide"
    cfg["serving"]["num_slots"] = 96
    (bench / "configs" / "qwen2-0.5b-wide.json").write_text(json.dumps(cfg))
    mix = {"loop": "open", "rate": 3.5,
           "prompt_len": {"values": [64], "probs": [1.0]},
           "gen_len": {"values": [32], "probs": [1.0]},
           "warmup_s": 2.0, "follow_s": 30.0}
    (bench / "traffic" / "short-open.json").write_text(json.dumps(mix))
    (bench / "metrics" / "window_s.py").write_text(
        "def read(run):\n    return run.seconds\n")
    spec = _spec()
    spec["workloads"].append({"name": "qwen2-short", "config":
                              "qwen2-0.5b-wide", "traffic": "short-open",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "window_s", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "test", "moves": "setup_s",
                              "workloads": ["qwen2-short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    run = _load_run(bench)
    cell, config, traffic, e2e, layer = run.cell_spec(
        "qwen2-short", str(tmp_path / "BENCHMARK.json"), str(bench))
    read = run.reader("window_s")
    assert config["serving"]["num_slots"] == 96
    assert traffic["rate"] == 3.5
    assert [m["name"] for m in layer][-1] == "window_s"
    assert {m["name"] for m in e2e} == {"setup_s"}
    assert read(type("Run", (), {"seconds": 7.0})) == 7.0
    assert run.reference_of(config).__file__ == str(bench / "reference.py")
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file the benchmark had was edited


def _toy_copy(tmp_path):
    """The copy with the toy architecture's reference module added."""
    bench, before = _copy(tmp_path)
    shutil.copy(os.path.join(DATA, TOY + ".py"), bench / (TOY + ".py"))
    return bench, before


def _toy_config():
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    return {**cfg, "name": "toy", "reference": TOY}


def _grid_record(prompt_len=6, gen=16, mask_id=255):
    """A finished request whose commits follow the toy's absolute grid of
    4: blocks [6, 8), [8, 12), [12, 16), [16, 20), [20, 22), one position a
    step, left to right; tick i is the i-th commit."""
    prompt = list(range(1, prompt_len + 1))
    cuts = [prompt_len, 8, 12, 16, 20, prompt_len + gen]
    events, tokens = [], []
    for b, (a, z) in enumerate(zip(cuts, cuts[1:])):
        for t, p in enumerate(range(a, z)):
            tok = (7 * p) % (mask_id - 1) + 1
            events.append([20.0 + len(events), len(events) + 1, b, t, [p],
                           [tok]])
            tokens.append(tok)
    return {"i": 0, "due": 10.0, "sent": 10.0, "prompt": prompt, "gen": gen,
            "events": events, "done": 50.0, "tokens": tokens,
            "status": "ok"}


def test_commits_follow_the_configured_grid(tmp_path):
    """Commits on an absolute grid break none of the toy's blocks and
    many of the default grid's, whose blocks start at the prompt's end."""
    bench, _ = _toy_copy(tmp_path)
    run = _load_run(bench)
    toy, rec = _toy_config(), _grid_record()
    rows, bad, final = run.commits(rec, toy)
    assert bad == 0
    assert [(r[3], r[4]) for r in rows[:3]] == [(6, 8), (6, 8), (8, 12)]
    assert final[6:].tolist() == rec["tokens"]
    default = {k: v for k, v in toy.items() if k != "reference"}
    _, bad, _ = run.commits(rec, default)
    assert bad > 0
    # the short first block's second step takes the next block's first
    # position, and that block's first step the position left behind
    ev = rec["events"]
    ev[1][4], ev[2][4] = ev[2][4], ev[1][4]
    _, bad, _ = run.commits(rec, toy)
    assert bad == 2


def test_a_new_architecture_needs_no_edit(tmp_path, monkeypatch):
    """A toy architecture (one more parameter leaf, block-causal attention
    on an absolute grid of 4 with a short first block, its own tick count)
    with its configuration, cell and a kernel-roofline reader, all new
    files: the cell's configuration, the layout check of ``build``, the
    commit check, the judge and the per-layer readers reach the toy
    module, and no file the benchmark had changes."""
    bench, before = _toy_copy(tmp_path)
    (bench / "configs" / "toy.json").write_text(json.dumps(_toy_config()))
    shutil.copy(os.path.join(DATA, "traffic", "tiny-closed.json"),
                bench / "traffic" / "toy-closed.json")
    (bench / "metrics" / "toy_attn_roofline.py").write_text(
        '"""The toy attention kernel\'s share of its roofline: a 4-row block\n'
        'over 64 keys a call (percent).  Layer: kernels."""\n'
        "import counts\n\n\n"
        "def read(run):\n"
        "    share = []\n"
        "    for d in run.trace['devices'].values():\n"
        "        k = d['kernels'].get('toy_attn')\n"
        "        if k:\n"
        "            share.append(k['calls'] * counts.attn_flops(\n"
        "                run.model, 4, 64) / run.peaks['bf16_flops_s']\n"
        "                / k['s'])\n"
        "    return 100.0 * sum(share) / len(share) if share else None\n")
    spec = _spec()
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "chipbench/configs/toy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-closed", "config": "toy",
                              "traffic": "toy-closed", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "tokens_s":
            m["workloads"].append("toy-closed")
    for name, layer in (("toy_attn_roofline", "kernels"),
                        ("tick_mfu.toy", "tick on device")):
        spec["per_layer"].append({"name": name, "unit": "%",
                                  "better": "higher",
                                  "source": "device_trace", "layer": layer,
                                  "moves": "tokens_s",
                                  "workloads": ["toy-closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    run = _load_run(bench)
    cell, config, traffic, e2e, layer = run.cell_spec(
        "toy-closed", str(tmp_path / "BENCHMARK.json"), str(bench))
    toy = run.reference_of(config)
    assert toy.__file__ == str(bench / (TOY + ".py"))
    assert {m["name"] for m in e2e} == {"tokens_s", "setup_s"}
    assert [m["name"] for m in layer] == ["toy_attn_roofline",
                                          "tick_mfu.toy"]

    # the program has no per-head norm: the layout check is the toy's
    with pytest.raises(RuntimeError, match=f"{TOY}.layout"):
        run.build(config, 7)
    assert toy.CALLS == ["init_weights"]

    rec = _grid_record()
    result = {"records": [rec], "window": [0.0, 100.0]}
    check, _ = run.judge(result, config, 7, closed=True)
    assert check["commit_mismatch"]["value"] == 0
    assert check["stream_mismatch"]["value"] == 0
    assert math.isfinite(check["max_gap"]["value"])
    assert toy.CALLS[1:] == ["blocks", "init_weights", "check_rows"]

    events = [
        [DEV, "XLA Ops", "%toy_attn.1 = custom-call()", 0, 1 * MS, ""],
        [DEV, "XLA Ops", "%fused_head_sampling.2 = custom-call()", 1 * MS,
         2 * MS, ""],
        [DEV, "XLA Ops", "%toy_attn.1 = custom-call()", 10 * MS, 11 * MS,
         ""],
        [DEV, "XLA Ops", "%fused_head_sampling.2 = custom-call()", 11 * MS,
         12 * MS, ""],
        [DEV, "XLA Modules", "jit_tick(1)", 0, 3 * MS, ""],
        [DEV, "XLA Modules", "jit_tick(1)", 10 * MS, 13 * MS, ""],
    ]
    monkeypatch.setattr(run.trace_reduce, "load", lambda _: events)
    result["trace"] = {"dir": str(tmp_path / "trace"), "seconds": 0.05,
                       "ticks": [[3], [5]]}
    result["scrapes"] = {}
    out = run.layer_metrics(result, config, traffic, layer, 3.0,
                            "TPU v5 lite")
    # two calls, each QK^T and PV: 4 x 2 layers x 64 wide x 4 queries x 64
    # keys, at 197 TFLOP/s
    least = 2 * (4 * 2 * 64 * 4 * 64) / 197e12
    assert out["toy_attn_roofline"]["value"] == pytest.approx(
        100.0 * least / 0.002)
    assert out["tick_mfu.toy"]["value"] > 0
    assert toy.CALLS[-1] == "tick_flops"

    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file the benchmark had was edited


@pytest.mark.parametrize("gen", [32, 64, 128])
@pytest.mark.parametrize("config", ["llada-8b-16l", "qwen2-0.5b"])
def test_the_default_grid_is_the_parents(config, gen):
    """``reference.blocks`` gives the grid ``run.py`` had before it asked
    the reference: blocks of L from the prompt's end, L spread over the T
    steps, the remainder first."""
    run = _load_run(Path(ROOT) / "chipbench")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    L, T = cfg["serving"]["block_length"], cfg["serving"]["steps_per_block"]
    ks = [L // T + (t < L % T) for t in range(T)]
    ref = run.reference_of(cfg)
    for prompt_len in range(16, 129):
        assert ref.blocks(prompt_len, gen, L, T) == [
            (prompt_len + b * L, prompt_len + (b + 1) * L, ks)
            for b in range(gen // L)]
