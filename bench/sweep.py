"""Find the knee of an open-loop cell: its mix at several fixed rates.

    python3 -m bench.sweep --workload smollm.chat --seconds 45 \\
        --rates 0.5 0.7 0.9

For each rate, one run of the cell with that arrival rate, in one
process (the programs compile once); the mix's lead of arrivals before
the window is served first, so each window opens at the rate's steady
state.  Each prints one JSON line: the end-to-end metrics, and
``backlog_at_open`` and ``backlog_at_close``, the requests due by the
window's opening and by its close still without a first token then.
The knee is the highest rate whose backlog does not grow over the
window; the cell then runs at about four fifths of it.
"""

from __future__ import annotations

import argparse
import json

from bench.cell import ROOT, run_cell


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    for rate in args.rates:
        res = run_cell(ROOT, args.workload, args.seed, args.seconds, False,
                       rate=rate)
        print(json.dumps({"rate_per_s": rate, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "backlog_at_open": res["backlog_at_open"],
                          "backlog_at_close": res["backlog_at_close"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
