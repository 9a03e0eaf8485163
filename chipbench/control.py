"""The control of the commit-verification cells: the run.py flow with
the plain reference, its signature guarantee dropped (tally only), in
the program's place. The comparison that decides `correct` has to fail
it: `verdict_mismatches` reads the window's corrupted requests, over a
limit of 0.

    python3 chipbench/control.py --workload <cell> --seed <n> --seconds <s>

Run on the chip at the cell's own size on three seeds or more
(prove.sh does); the benchmark's own runs never run it. Exit code 0
means the control FAILED the comparison, as it must.
"""

from __future__ import annotations

import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = harness.argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    args.trace = 0
    try:
        result = harness.run_cell(args, prepare=lambda d: d.use_control())
    except harness.Refused as e:
        print(f"chipbench control: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"control": True, "correct": result["correct"],
                      "attempted": result["attempted"], "checks": result["checks"]}))
    return 0 if not result["correct"] and result["checks"]["verdict_mismatches"]["value"] > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
