#!/usr/bin/env python3
"""Readings a cell's limits are set from: per seed, the widest and the mean
logit gap of the served tokens (the program) and of the tokens the fp8 control
ranks first at the same positions, per model.

    python3 bench/readings.py --workload mamba2.chat --seeds 11,12,13 \\
        --seconds 10 [--control N]

One process, one set-up: each seed serves one window of the cell's own
schedule at its own load and sizes, then the same sample of tasks that
``run.py`` checks goes through the float32 reference (and, with
``--control N``, on the first N seeds, the fp8 one).  The benchmark's own runs never run the
control.  One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="also read the control on the first N seeds")
    args = ap.parse_args()
    import check
    bench = run.load_json("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = run.load_json(conf["file"])
    traffic = run.load_json(f"bench/traffic/{cell['traffic']}.json")
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        c = run.Cell(cell, config, traffic, seeds[0])
    except run.NoChip as e:
        run.log(f"no result: {e}")
        return 3
    for n, seed in enumerate(seeds):
        w = c.window(seed, args.seconds, trace=False)
        bad = run.failures(w)
        out = {"seed": seed, "unserved": bad}
        if not bad:
            picked = check.sample(w.tasks, seed)
            t0 = time.perf_counter()
            out["program"] = check.gap_numbers(w.models, w.tasks, w.records,
                                               picked)
            out["reference_s"] = time.perf_counter() - t0
            if n < args.control:
                out["control"] = check.gap_numbers(w.models, w.tasks,
                                                   w.records, picked,
                                                   low=True)
        print(json.dumps(out), flush=True)
    c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
