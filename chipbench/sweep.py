"""Offer an open-loop cell's traffic at several fixed rates, to find the
highest rate the system sustains without a growing backlog (its knee).

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 6 8 10 12

One process; each rate is one window of the cell's traffic mix with only
its rate changed.  Prints per rate the end-to-end metrics, the requests
due and completed in the window, and the mean queue wait of the first and
the last third of the window's requests: a wait that grows through the
window is a backlog.  A cell's traffic file then fixes its rate as a number;
the benchmark's runs never search for one.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args(argv)
    cell, config, traffic, _, _ = run_lib.cell_spec(a.workload)
    run_lib.arm_cache()
    clock = run_lib.CompileClock()
    os.makedirs(run_lib.WORK_DIR, exist_ok=True)
    for rate in a.rates:
        path = os.path.join(run_lib.WORK_DIR, "sweep-traffic.json")
        with open(path, "w") as f:
            json.dump({**traffic, "rate": rate}, f)
        run = run_lib.drive(config, path, a.seed, a.seconds, False, clock)
        vals = run_lib.end_to_end(run)
        due = sorted((r for r in run["records"]
                      if run_lib.in_window(run, r["due"])),
                     key=lambda r: r["due"])
        done = [r for r in due if r.get("status") == "ok"]
        third = max(1, len(due) // 3)
        first = [r["events"][0][0] - r["due"] for r in due[:third]
                 if r["events"]]
        last = [r["events"][0][0] - r["due"] for r in due[-third:]
                if r["events"]]
        print(json.dumps({
            "rate": rate, "due": len(due), "completed": len(done),
            "ttft_first_third_s": sum(first) / max(1, len(first)),
            "ttft_last_third_s": sum(last) / max(1, len(last)),
            **vals}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
