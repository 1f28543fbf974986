"""Readings that the output check's limits are set from.

    python3 -m bench.control --workload smollm.chat --seconds 30 \\
        --seeds 101 102 103 --runs program quantize reference_int8

For each seed, the runs named: ``program`` is one run of the cell as
the benchmark makes it; ``quantize`` is the same program with its int8
weights switched on, a precision below the bfloat16 the configuration
states; ``reference_int8`` serves as the program does and puts the
reference at int8 weights in the served tokens' place for the output
check.  Every run prints one JSON line with its ``correct`` and the
readings of its output check; all runs share one process, so the
programs compile once.  The limit of a cell lies above the largest gap
of the program's own runs and below the smallest gap of a control, and
a control's run comes out not correct.
"""

from __future__ import annotations

import argparse
import json
import sys

from bench.cell import ROOT, run_cell

CONTROLS = {
    "program": {},
    "quantize": {"overrides": {"quantize": True}},    # int8 weights
    "reference_int8": {"control": True},
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--runs", nargs="+", default=["program"],
                    choices=sorted(CONTROLS))
    args = ap.parse_args()
    for seed in args.seeds:
        for name in args.runs:
            res = run_cell(ROOT, args.workload, seed, args.seconds, False,
                           **CONTROLS[name])
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "run": name, "correct": res["correct"],
                              "metrics": res["metrics"],
                              "readings": res["readings"],
                              "check": res["check"]}), flush=True)
    sys.exit(0)


if __name__ == "__main__":
    main()
