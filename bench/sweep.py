"""Sweep the offered rate of an open-loop cell, to find once the highest
rate the service sustains (the cell's traffic file then fixes a rate below
it). All rates in one process; each run prints its latencies and how long
the queue took to drain after its window.

    python3 bench/sweep.py --workload table1-poisson --seed 7000 \\
        --seconds 5 --rates 1000,2000,3000
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        r = run.run_cell(args.workload, args.seed + i, args.seconds, False,
                         mix_update={"rate_per_s": rate})
        print("rate", rate, json.dumps({"attempted": r["attempted"],
                                        "correct": r["correct"],
                                        "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
