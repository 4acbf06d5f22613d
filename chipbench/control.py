"""The check's two readings for a cell: the program's and the control's.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n1> <n2> ...

For each seed, one short window of the cell's own traffic at its own size,
then the reference over the served tokens of the same sample a run checks,
and the control over the same canvases: the reference with its matmuls in
float8 e4m3 and its sampling in MXFP4, one step below the bfloat16 forward
and MXFP8 sampling the configuration states.  Prints, per seed, each
compared number for the program and for the control; the limits in the
configuration files are set between the two.  The benchmark's runs do not
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run as run_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark", default=os.path.join(run_lib.ROOT,
                                                        "BENCHMARK.json"))
    ap.add_argument("--root", default=run_lib.HERE)
    a = ap.parse_args(argv)
    cell, config, traffic, _, _ = run_lib.cell_spec(a.workload, a.benchmark,
                                                    a.root)
    path = os.path.join(a.root, "traffic", cell["traffic"] + ".json")
    run_lib.arm_cache()
    clock = run_lib.CompileClock()
    closed = traffic["loop"] == "closed"
    for seed in a.seeds:
        run = run_lib.drive(config, path, seed, a.seconds, False, clock)
        check, ctl = run_lib.judge(run, config, seed, closed, control=True)
        print(json.dumps({"seed": seed, "program": {
            k: c["value"] for k, c in check.items()}, "control": ctl}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
