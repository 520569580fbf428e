#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference computed in
the precision below the configuration's, put in the program's place.

    python3 benchmarks/chip/control.py --workload flow_1080p.cameras \\
        --seeds 11 12 13

For each seed it makes the run's frame pool at the cell's own size,
takes ``check_frames`` frames of it (as many as a run compares), and
reads the comparison of the control's answers against the reference's.
Each line gives the readings beside the configuration's limits; the
control must come out not correct on every seed.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import compare
import run
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    pipe = cell.cfg["pipeline"]
    k = int(cell.cfg["check_frames"])
    worst = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        n = traffic.pool_size(cell.mix, cell.chips)
        pairs = traffic.make_pool(cell.mix, pipe["h"], pipe["w"], n, seed)
        pick = traffic.order(n, k, seed)
        got = [(cell.ref.control(*pairs[p], **pipe),
                cell.ref.reference(*pairs[p], **pipe)) for p in pick]
        ok, checks = compare.verdict(compare.readings(got, 0),
                                     cell.cfg["limits"])
        worst = worst and not ok
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": ok, "checks": checks,
                          "frames": k, "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0 if worst else 1


if __name__ == "__main__":
    sys.exit(main())
