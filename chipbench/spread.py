"""Summarise the runs a prove.sh call left in a directory: each
metric's median and its spread — the distance between the first and
the third quartile (statistics.quantiles, n=4) as a share of the
median — for each set of runs, and `correct` of every run.

    python3 chipbench/spread.py chiprun_out/prove/<cell>
"""

from __future__ import annotations

import json
import os
import statistics
import sys


def last_line(path: str):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def main(directory: str) -> int:
    groups: dict = {}
    for name in sorted(os.listdir(directory)):
        if not name.endswith(".out"):
            continue
        row = last_line(os.path.join(directory, name))
        if row is None:
            print(f"{name}: no result line")
            continue
        groups.setdefault(name.split(".")[0], []).append((name, row))
    for group, rows in groups.items():
        ok = [r.get("correct") for _n, r in rows]
        print(f"{group}: {len(rows)} runs, correct {ok.count(True)}/{len(ok)}")
        values: dict = {}
        for _n, r in rows:
            for metric, m in (r.get("metrics") or {}).items():
                values.setdefault(metric, []).append(m["value"])
        for metric, vs in values.items():
            med = statistics.median(vs)
            line = f"  {metric}: median {med:.6g} n={len(vs)}"
            if len(vs) >= 4 and med:
                q = statistics.quantiles(vs, n=4)
                line += f" spread {(q[2] - q[0]) / med:.4%}"
            print(line + "  " + " ".join(f"{v:.5g}" for v in vs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
