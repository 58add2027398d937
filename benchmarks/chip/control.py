#!/usr/bin/env python3
"""Readings of the reference's numbers for the sound program and for its
control, several seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--sides sound control]

The control is the program built on a round twice the configuration's
``dt`` (the step that would double ``sim_s_per_s``), while the reference
still holds it to the stated ``dt``; it has to come out not correct.  Each
run prints one JSON line: the side, the seed, ``correct`` and the numbers
with their limits.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sides", nargs="+", default=["sound", "control"],
                    choices=["sound", "control"])
    args = ap.parse_args(argv)

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, config, traffic = run.find_cell(args.workload, bench)
    metrics = run.cell_metrics(cell["name"], bench, False)
    sys.path[:0] = [str(run.ROOT / "src"), str(run.HERE)]
    devices = run.chips(cell["chips"])
    run.enable_cache()
    coarse = {**config["cluster"], "dt": 2 * config["cluster"]["dt"]}
    for seed in args.seeds:
        for side in args.sides:
            t0 = time.perf_counter()
            out = run.run_cell(
                config, traffic, metrics, seed=seed,
                seconds=args.seconds, trace=False, devices=devices,
                program_cluster=coarse if side == "control" else None, t0=t0)
            print(json.dumps({"side": side, "seed": seed,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "metrics": out["metrics"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
