"""Read the limits' two ends for one cell: the program's readings and the
control's, seed by seed, in one process.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed it makes one run of the cell as the benchmark does, with a
short window, and prints the numbers its check compared; then the same
inputs once more with the reference's control (the one the cell's configuration
names, ``bench/reference.py``) in the program's place. The
program's readings are the lower ends of the limits, the control's the
upper ends. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench.run import run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        for control in (False, True):
            r = run_cell(args.workload, seed, args.seconds, False,
                         control=control)
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "side": "control" if control else "program",
                "correct": r["correct"], "attempted": r["attempted"],
                "checks": r["checks"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
