"""Two faults planted in the node cells' timed path, beside the control
(control.py): the run.py flow with the program broken in one place
before the window, which the comparison that decides `correct` has to
fail.

    python3 chipbench/node_faults.py --workload <cell> --seed <n> --seconds <s> --fault <name>

  light-skipped   block sync's `verify_commit_light` accepts whatever it
                  is handed: a block is stored on the word of a commit
                  nobody checked (guarantee 1), the corrupted pair goes
                  through, and no full verification finds a vote in the
                  cache
  cache-blind     the verified-signature cache answers every bulk probe
                  with nothing: each verdict stays right and every full
                  verification sends all its votes to the device, a
                  slower result the counters tell from the deployed one

Run on the chip at the cell's own size; the benchmark's own runs never
run it. Exit code 0 means the fault FAILED the comparison, as it must.
"""

from __future__ import annotations

import json
import sys

try:  # imported by a test, which patches this very module
    from chipbench import run as harness
except ImportError:  # run as a script, from chipbench/
    import run as harness


def light_skipped(_driver, put=setattr) -> None:
    """`put` is how the attribute is set: a test hands in one that is
    undone after it."""
    from tendermint_tpu.blocksync import reactor

    put(reactor, "verify_commit_light", lambda *args, **kwargs: None)


def cache_blind(_driver, put=setattr) -> None:
    from tendermint_tpu.crypto import sigcache

    put(sigcache, "seen_keys_bulk", lambda keys: set())


FAULTS = {"light-skipped": light_skipped, "cache-blind": cache_blind}


def main(argv=None) -> int:
    ap = harness.argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    args = ap.parse_args(argv)
    args.trace = 0
    try:
        result = harness.run_cell(args, prepare=FAULTS[args.fault])
    except harness.Refused as e:
        print(f"chipbench node_faults: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"fault": args.fault, "correct": result["correct"],
                      "attempted": result["attempted"], "failed": result["failed"],
                      "checks": result["checks"]}))  # fmt: skip
    return 0 if not result["correct"] and result["failed"] > 0 else 1


if __name__ == "__main__":
    sys.exit(main())
