"""A cell, configuration, traffic mix or per-layer metric is added as a new
file and found by name, with no other file edited."""
import importlib.util
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
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
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["workloads"].append({"name": "qwen2-short", "config":
                              "qwen2-0.5b-wide", "traffic": "short-open",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "window_s", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "test", "moves": "setup_s",
                              "workloads": ["qwen2-short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_run", bench / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell, config, traffic, e2e, layer = run.cell_spec(
        "qwen2-short", str(tmp_path / "BENCHMARK.json"), str(bench))
    read = run.reader("window_s")
    assert config["serving"]["num_slots"] == 96
    assert traffic["rate"] == 3.5
    assert [m["name"] for m in layer][-1] == "window_s"
    assert {m["name"] for m in e2e} == {"setup_s"}
    assert read(type("Run", (), {"seconds": 7.0})) == 7.0
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file the benchmark had was edited
