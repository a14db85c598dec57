"""Spread of each metric over sets of runs, as the bounds are set from it:
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 perfbench/tools/spread.py A:runs_a/*.out B:runs_b/*.out

Each file's last line is one run's result line; each ``NAME:glob``
argument is one set.  Prints, per metric, each set's median and spread,
and the wider spread.
"""

from __future__ import annotations

import glob
import json
import statistics
import sys


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> int:
    sets = {}
    for arg in argv:
        name, pattern = arg.split(":", 1)
        lines = []
        for path in sorted(glob.glob(pattern)):
            with open(path) as f:
                last = f.read().strip().splitlines()
            if last:
                lines.append(json.loads(last[-1]))
        sets[name] = lines
    metrics = sorted({m for lines in sets.values() for ln in lines
                      for m in ln["metrics"]})
    for m in metrics:
        row = {}
        for name, lines in sets.items():
            vals = [ln["metrics"][m]["value"] for ln in lines
                    if m in ln["metrics"]]
            if len(vals) >= 2:
                row[name] = {"n": len(vals),
                             "median": statistics.median(vals),
                             "spread": spread(vals),
                             "min": min(vals), "max": max(vals)}
        widest = max((r["spread"] for r in row.values()), default=None)
        print(json.dumps({"metric": m, "sets": row, "widest": widest,
                          "correct": all(ln["correct"] for lines in
                                         sets.values() for ln in lines)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
