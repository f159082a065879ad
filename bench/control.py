"""Readings that set the limits of ``correct``, on the chip at a cell's own
size: the numbers compared, on sound runs of the service over many seeds
(the lower reading is their largest) and on the control, the service at the lower
precisions of the configuration's ``control`` block (the upper reading is
its smallest). All runs in one process; a short window each.

    python3 bench/control.py --workload whype-closed --first-seed 5000 \\
        --seeds 12 --control-seeds 3 --seconds 3
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    readings = {"sound": [], "control": []}
    seed = args.first_seed
    for kind, n in (("sound", args.seeds), ("control", args.control_seeds)):
        for _ in range(n):
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             rehearse=args.rehearse,
                             control=kind == "control")
            row = {k: v["value"] for k, v in r["checks"].items()}
            row.update(seed=seed, correct=r["correct"])
            readings[kind].append(row)
            print(kind, json.dumps(row), flush=True)
            seed += 1
    names = [k for k in readings["sound"][0] if k not in ("seed", "correct")]
    summary = {k: {"lower": max(r[k] for r in readings["sound"]),
                   "upper": min((r[k] for r in readings["control"]),
                                default=None)} for k in names}
    print("summary", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
