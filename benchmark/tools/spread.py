#!/usr/bin/env python3
"""Medians and spreads of repeated runs, the way the driver reads them.

Each input file holds the last lines of ``benchmark/run.py --trace 0`` runs
of ONE cell, one JSON object per line, in the order they ran. The runs are
cut into sets of ``--set-size``; per set and metric this prints the median
and the spread (distance between the quartiles over the median), then per
metric the wider spread and five times it: the rule the bounds in
BENCHMARK.json were set by.

    python3 benchmark/tools/spread.py chiprun_out/measure/*.jsonl --set-size 3
"""

import argparse
import json
import statistics


def quartile_spread(values):
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--set-size", type=int, default=6)
    args = ap.parse_args(argv)
    widest = {}
    for path in args.files:
        with open(path, encoding="utf-8") as f:
            lines = [json.loads(x) for x in f if x.strip().startswith("{")]
        sets = [lines[i:i + args.set_size]
                for i in range(0, len(lines), args.set_size)]
        print(f"{path}: {len(lines)} runs, correct "
              f"{sum(x['correct'] for x in lines)}, failed "
              f"{sum(x['failed'] for x in lines)}, steps "
              f"{[x['attempted'] for x in lines]}")
        for name in lines[0]["metrics"]:
            row = []
            for s in sets:
                vals = [x["metrics"][name]["value"] for x in s]
                sp = quartile_spread(vals)
                row.append(f"median {statistics.median(vals):.6g} spread "
                           f"{100 * sp:.3f}% (n={len(vals)})")
                widest[name] = max(widest.get(name, 0.0), sp)
            print(f"  {name}: " + " | ".join(row))
    print("widest spread over all cells and sets -> 5x:")
    for name, sp in widest.items():
        print(f"  {name}: {100 * sp:.3f}% -> {100 * 5 * sp:.2f}%")


if __name__ == "__main__":
    main()
