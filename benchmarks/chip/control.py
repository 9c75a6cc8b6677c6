"""Readings of the correctness control at a cell's own size.

    python3 benchmarks/chip/control.py --workload paper_n64.table3_uniform \
        --seeds 5 6 7

The control is the plain reference with one guarantee of the
configuration broken, put in the program's place: the allocator's
rotating priority is frozen.  For each seed this lays the cell out as a
run does, draws the same sample of scenarios, and prints one JSON line
with the harness's comparison of the control's counters against the
reference's.  The benchmark's own runs never run it; it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmarks.chip import harness as H
    cell = H.load_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        planned = H.plan_cell(cell, seed)
        padded = H.padded_scenarios(H.experiment(cell, planned))
        sample = H.check_sample(cell, planned, padded, seed)
        refs = {i: H.reference_counters(cell, planned[i]) for i in sample}
        control = {i: H.reference_counters(cell, planned[i], rotate=False)
                   for i in sample}
        checks, compared = H.compare([control], refs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "scenarios": [planned[i].topology + "/" +
                                        planned[i].substrate + "/" +
                                        planned[i].pattern for i in sample],
                          "control": checks, "compared": compared,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
