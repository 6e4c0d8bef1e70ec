"""Spread of each metric over sets of runs, as the contract measures it:
the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python -m benchmark.tests.spread chiprun_out/<cell>_setA.results ...

Each file holds lines ``RESULT ... {result line}`` of one set."""

from __future__ import annotations

import json
import statistics
import sys


def read(path):
    runs = []
    with open(path) as f:
        for line in f:
            if line.startswith("RESULT") and "{" in line:
                runs.append(json.loads(line[line.index("{"):]))
    return runs


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    for path in paths:
        runs = read(path)
        print(f"{path}: {len(runs)} runs, correct "
              f"{[r['correct'] for r in runs]}, peak GB "
              f"{[round(r['device']['memory_peak_bytes'] / 1e9, 2) for r in runs]}")
        for name in runs[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            body = vals[1:] if name == "setup_s" and len(vals) > 2 else vals
            print(f"  {name}: median {statistics.median(body):.6g} spread "
                  f"{100 * spread(body):.3f}% values "
                  f"{[round(v, 4) for v in vals]}")


if __name__ == "__main__":
    main(sys.argv[1:])
