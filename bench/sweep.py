#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the highest rate it sustains.

  python3 bench/sweep.py --workload <cell> --rates 2,3,4 --seconds 30

Runs the cell once per rate in one process, with the mix's ``rate_rps``
overridden, and prints one JSON line per rate: the end-to-end metrics, the
requests due, and the backlog (requests still queued) at the window's
middle and at its close.  A rate above the knee leaves a backlog that
grows through the window; give it a window longer than a request's life.
The requests under way at the warm-up's start scale with the rate
(``life_s`` of the mix stays).  The cell's traffic file then fixes its
rate; no run searches.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench import harness
    cell = harness.resolve(args.workload)
    device = harness.check_device(cell.chips)
    harness.enable_cache()
    for rate in map(float, args.rates.split(",")):
        at = dataclasses.replace(cell, mix={**cell.mix, "rate_rps": rate})
        out = harness.window(at, args.seed, args.seconds, False,
                             t_start=time.time())
        line = harness.result(at, device, out, args.seed, False)
        print(json.dumps({"rate_rps": rate, "correct": line["correct"],
                          **line["load"],
                          "metrics": {k: v["value"] for k, v in
                                      line["metrics"].items()}}), flush=True)
    return 0
