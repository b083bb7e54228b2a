"""Reads what benchmark/rehearse/measure.sh wrote and prints, per cell and
end-to-end metric, each set's median and spread (the distance between the
quartiles over the median), the wider of the two, and how far the second
set's median is from the first's — the figures a bound is set from.

    python3 benchmark/rehearse/spread.py [chiprun_out/benchmark/measure]
"""

import glob
import json
import os
import statistics
import sys


def quartiles(values):
    """Quartiles by linear interpolation between order statistics; of one
    value, that value three times."""
    if len(values) < 2:
        return [values[0]] * 3
    return statistics.quantiles(values, n=4, method="inclusive")


def main(argv):
    root = argv[1] if len(argv) > 1 else os.path.join("chiprun_out", "benchmark", "measure")
    for path in sorted(glob.glob(os.path.join(root, "*.jsonl"))):
        rows = [json.loads(line) for line in open(path) if line.strip()]
        cell = os.path.basename(path)[:-len(".jsonl")]
        runs = [r for r in rows if r["set"] in (1, 2)]
        print(f"{cell}: {len(runs)} untraced runs, correct in "
              f"{sum(1 for r in runs if r['line']['correct'])}")
        names = sorted({m for r in runs for m in r["line"]["metrics"]})
        for name in names:
            medians, spreads = [], []
            for s in (1, 2):
                values = [r["line"]["metrics"][name]["value"] for r in runs if r["set"] == s]
                if name == "setup_s":
                    values = values[1:] if s == 1 else values   # the first run compiles
                q1, q2, q3 = quartiles(values)
                medians.append(q2)
                spreads.append((q3 - q1) / q2)
                print(f"  {name} set {s}: median {q2:.6g}, spread {100 * spreads[-1]:.3f}% "
                      f"(n={len(values)}, min {min(values):.6g}, max {max(values):.6g})")
            print(f"  {name}: wider spread {100 * max(spreads):.3f}%, second median "
                  f"{100 * (medians[1] / medians[0] - 1):+.3f}% of the first")
        for r in rows:
            if r["set"] == 0:
                print("  traced:", json.dumps({k: round(v["value"], 4) for k, v in
                                               r["line"]["metrics"].items()}))
                print("  traced device:", r["line"]["device"])


if __name__ == "__main__":
    main(sys.argv)
