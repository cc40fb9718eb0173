#!/usr/bin/env python3
"""Spreads of a cell's runs, as the benchmark's contract reckons them.

    python3 benchmark/tools/spread.py --set a1.txt,a2.txt,... --set b1.txt,...

Each file is one run's standard output (its last line is the result).
For every metric: each set's median and spread (the distance between
the first and third quartile of `statistics.quantiles(values, n=4)` as a
share of the median), the wider of the two, five times that (what a
bound is set to, never under 1%), and the second set's median against
the first's.
"""

import argparse
import json
import statistics
import sys


def result_of(path):
    last = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"correct"' in line:
                last = line
    if last is None:
        raise SystemExit(f"{path}: no result line")
    return json.loads(last)


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--set", action="append", required=True)
    args = ap.parse_args()
    sets = [[result_of(p) for p in s.split(",")] for s in args.set]
    bad = [r for s in sets for r in s if not r["correct"] or r["failed"]]
    names = sorted({n for s in sets for r in s for n in r["metrics"]})
    out = {"runs": [len(s) for s in sets], "not_correct_or_failed": len(bad),
           "memory_peak_bytes": max(r["device"]["memory_peak_bytes"]
                                    for s in sets for r in s)}
    for n in names:
        per = [[r["metrics"][n]["value"] for r in s if n in r["metrics"]]
               for s in sets]
        med = [statistics.median(v) for v in per]
        spr = [spread(v) if len(v) >= 2 else None for v in per]
        widest = max(x for x in spr if x is not None)
        out[n] = {"medians": med, "spreads": spr, "widest": widest,
                  "five_times": max(0.01, 5 * widest),
                  "second_vs_first": (med[1] / med[0] - 1.0
                                      if len(med) > 1 else None),
                  "values": per}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
