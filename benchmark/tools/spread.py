#!/usr/bin/env python3
"""Medians and spreads of repeated runs, as the driver reads them: for each
metric the distance between the quartiles over the median, per set of runs.

    python benchmark/tools/spread.py results.jsonl [--sets 2]

``results.jsonl`` holds the last lines of runs of ONE cell, in the order they
were made; ``--sets n`` splits them into n consecutive sets.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("file")
    ap.add_argument("--sets", type=int, default=1)
    args = ap.parse_args(argv)
    with open(args.file) as f:
        runs = [json.loads(line) for line in f if line.startswith("{")]
    print(f"{len(runs)} runs; correct: {[r['correct'] for r in runs]}; "
          f"failed: {[r['failed'] for r in runs]}")
    size = -(-len(runs) // args.sets)
    for name in runs[0]["metrics"]:
        for i in range(args.sets):
            values = np.array([r["metrics"][name]["value"]
                               for r in runs[i * size:(i + 1) * size]
                               if name in r["metrics"]])
            if not len(values):
                continue
            q1, med, q3 = np.percentile(values, [25, 50, 75])
            print(f"{name:24s} set {i}: n={len(values)} median={med:.6g} "
                  f"spread={(q3 - q1) / med:.4%} min={values.min():.6g} "
                  f"max={values.max():.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
