#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
      [--control float8_e4m3fn]

Runs the cell once per seed in one process (the programs compile once), at
the cell's own load and sizes with a window of ``--seconds``, and prints
one JSON line per seed: the program's numbers compared, beside the cell's
limits, and with ``--control`` the control's (the reference computed in
that precision, on the same sample), with whether each comes out
correct.  The benchmark's own runs never run the control.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args()
    from bench import check, harness
    cell = harness.resolve(args.workload)
    device = harness.check_device(cell.chips)
    harness.enable_cache()
    for seed in map(int, args.seeds.split(",")):
        out = harness.window(cell, seed, args.seconds, False,
                             t_start=time.time())
        line = harness.result(cell, device, out, seed, False)
        row = {"seed": seed, "correct": line["correct"],
               "checks": line["checks"], "load": line["load"],
               "metrics": {k: v["value"] for k, v in line["metrics"].items()}}
        if args.control:
            ctl = check.control(cell.cfg, seed, out.served, cell.limits,
                                args.control)
            row["control"] = {"correct": check.passed(ctl), "checks": ctl}
        print(json.dumps(row), flush=True)
    return 0
