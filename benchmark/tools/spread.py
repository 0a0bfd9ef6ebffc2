#!/usr/bin/env python3
"""Quartile spreads of result lines, as the contract measures them: for
each metric the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmark/tools/spread.py set1.jsonl [set2.jsonl ...]

Each file holds the last lines of one set's runs (one JSON object a line).
"""

import json
import statistics
import sys


def spreads(lines):
    by = {}
    for line in lines:
        for name, m in line["metrics"].items():
            by.setdefault(name, []).append(m["value"])
    out = {}
    for name, values in by.items():
        med = statistics.median(values)
        if len(values) >= 2:
            q = statistics.quantiles(values, n=4)
            out[name] = {"n": len(values), "median": med,
                         "spread": (q[2] - q[0]) / med,
                         "min": min(values), "max": max(values)}
        else:
            out[name] = {"n": 1, "median": med, "spread": None,
                         "min": med, "max": med}
    return out


def main(paths):
    for path in paths:
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.startswith("{")]
        lines = [l for l in lines if "metrics" in l]
        print(path, "runs", len(lines), "correct",
              sum(bool(l.get("correct")) for l in lines))
        for name, s in spreads(lines).items():
            print(f"  {name}: median {s['median']:.6g} spread "
                  f"{s['spread'] if s['spread'] is None else round(100 * s['spread'], 3)}% "
                  f"min {s['min']:.6g} max {s['max']:.6g} (n={s['n']})")


if __name__ == "__main__":
    main(sys.argv[1:])
