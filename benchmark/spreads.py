"""How the bounds were set (by hand, from files of result lines):

    python benchmark/spreads.py <set A files> -- <set B files>

Each file holds the standard output of one run of one cell.  For every
metric of the result lines: each set's median and spread (interquartile
distance over the median, as ``statistics.quantiles`` gives the
quartiles), the wider of the two, five times it, and the second median
against the first.
"""
from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from harness import stats


def result_line(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.loads(f.read().strip().splitlines()[-1])


def main(argv) -> int:
    cut = argv.index("--")
    sets = [[result_line(p) for p in argv[:cut]],
            [result_line(p) for p in argv[cut + 1:]]]
    wrong = sum(not r["correct"] for s in sets for r in s)
    if wrong:
        print(f"note: {wrong} of these runs came out not correct")
    for name in sets[0][0]["metrics"]:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        med = [statistics.median(v) for v in vals]
        spread = [stats.iqr_spread(v) for v in vals]
        print(f"{name}: medians {med[0]:.6g} {med[1]:.6g} "
              f"(second/first {med[1] / med[0] - 1:+.4f}); spreads "
              f"{spread[0]:.4f} {spread[1]:.4f}; five times the wider "
              f"{5 * max(spread):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
