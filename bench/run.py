#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the repository root, on the machine that holds the cell's chips.  It
checks the device (a TPU, as many chips as the cell asks for; nothing
falls back to the CPU), keeps JAX's compilation cache at
``<checkout>/.jax_cache``, makes the
weights and the traffic from ``--seed``, warms up, measures for
``--seconds`` (with ``--trace 1`` a traced window of at most 10 s), checks
the served tokens against the plain reference, and prints one JSON line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, the numbers compared
beside their limits.  Set ``BENCH_KEEP_TRACE=<dir>`` to keep the trace.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    line = harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
