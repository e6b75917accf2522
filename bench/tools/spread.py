#!/usr/bin/env python3
"""Medians and quartile spreads of result lines, per metric.

  python3 bench/tools/spread.py A1.out A2.out ... -- B1.out B2.out ...

Each file holds one run's standard output; its last line is the result.
Files before ``--`` form the first set, after it the second. For each
metric: the median of each set, its spread (distance between the first and
third quartile of ``statistics.quantiles(values, n=4)`` over the median),
the wider spread and five times it, the bound the contract asks for;
the check's two readings of a bound: for tightness the mean of the sets'
spreads, each set without its run farthest from the median (a bound under
twice it is too tight), for looseness the spread of all runs together (a
bound over eight times it is too loose).
"""
from __future__ import annotations

import json
import statistics
import sys


def result(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread(values[:far] + values[far + 1:])


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    cut = argv.index("--") if "--" in argv else len(argv)
    sets = [s for s in (argv[:cut], argv[cut + 1:]) if s]
    runs = [[result(p) for p in s] for s in sets]
    names = sorted({m for rs in runs for r in rs for m in r["metrics"]})
    for name in names:
        row = []
        widest = 0.0
        trimmed, pooled = [], []
        for rs in runs:
            vals = [r["metrics"][name]["value"] for r in rs
                    if name in r["metrics"]]
            if len(vals) < 2:
                continue
            sp = spread(vals)
            widest = max(widest, sp)
            pooled += vals
            if len(vals) >= 3:
                trimmed.append(trimmed_spread(vals))
            row.append(f"median {statistics.median(vals):.6g} spread "
                       f"{sp:.4%} (n={len(vals)})")
        tight = (f", tightness {statistics.mean(trimmed):.4%}" if trimmed
                 else "")
        loose = (f", all runs {spread(pooled):.4%}" if len(pooled) >= 2
                 else "")
        print(f"{name}: " + " | ".join(row)
              + f" | widest {widest:.4%}, x5 = {5 * widest:.4%}{tight}"
              + loose)
    correct = [r["correct"] for rs in runs for r in rs]
    print(f"correct: {sum(correct)}/{len(correct)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
