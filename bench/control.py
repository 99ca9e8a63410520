"""Readings that set the limits of ``correct``, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s> [--out readings.jsonl]

For each seed, in this one process: set-up, a window of ``--seconds`` at
the cell's own load, and the numbers the harness compares, for the
program; for each control seed the same numbers for the control, the
plain reference computed in bfloat16 and put in the program's place. One
JSON line per reading goes to standard output and, with ``--out``, is
appended to that file. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run as harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _, cell, cfg, traffic, limits = harness.find_cell(args.workload)
    harness.use_checkout()
    meter = harness.CompileMeter()
    devices = harness.require_devices(cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    with open(args.out or os.devnull, "a") as out:
        for seed in sorted(set(seeds) | controls):
            run = harness.new_run(cell, cfg, traffic, seed, devices)
            driver = harness.set_up(run, meter, time.perf_counter())
            harness.measure(run, driver, meter, args.seconds, 0)
            rows = []
            if seed in seeds:
                rows.append(("program", driver.check()))
            if seed in controls:
                rows.append(("control", driver.control()))
            for side, numbers in rows:
                rec = {"workload": args.workload, "side": side, "seed": seed,
                       "numbers": numbers, "limits": limits,
                       "calls": run.window["calls"], "device": run.device}
                print(json.dumps(rec), flush=True)
                out.write(json.dumps(rec) + "\n")
            del driver, run
    return 0


if __name__ == "__main__":
    sys.exit(main())
