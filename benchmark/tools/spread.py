#!/usr/bin/env python3
"""Medians and spreads of repeated runs, as the driver reads them: for each
metric the distance between the quartiles (``statistics.quantiles(v, n=4)``)
over the median, per set of runs and over all of them.

    python benchmark/tools/spread.py run1.out run2.out ... [--sets 2]
        [--choose itl_p95_ms itl_p90_ms itl_mean_ms itl_p50_ms]

Each file is the standard output of runs of ONE cell (one run a file, or
several in the order they were made); ``--sets n`` splits the runs into n
consecutive sets. Read of each run: its result line's metrics, and the numbers
of its ``window]`` log line (a serving driver prints every statistic of the
gaps there, judged or not).

``--choose`` applies the rule by which the chat cell's judged statistic was
picked (ISSUE 34, Tentpole 3; PERF.md, section 2): the first of the names
given whose widest spread (each set, and all runs) is under half of a bound
of at most 0.10, with the smallest of the bounds 0.04, 0.06, 0.08, 0.10 that
is more than twice that spread.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import sys

_NUMBER = re.compile(r"(\w+)=(-?\d+(?:\.\d+)?(?:e-?\d+)?)(?=\s|$)")


def runs_of(text: str):
    """One dict of numbers per run in ``text``: a run ends at its result
    line, and the ``window]`` line before it belongs to it."""
    out, extra = [], {}
    for line in text.splitlines():
        if "window]" in line:
            extra = {k: float(v) for k, v in _NUMBER.findall(line)}
        elif line.startswith("{") and '"metrics"' in line:
            result = json.loads(line)
            numbers = dict(extra, **{k: m["value"]
                                     for k, m in result["metrics"].items()})
            out.append({"correct": result["correct"],
                        "failed": result["failed"], "numbers": numbers})
            extra = {}
    return out


def spread(values) -> float:
    """Distance between the quartiles over the median, as the driver has it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else 0.0


def spreads(runs, name: str, sets: int):
    """The metric's spread in each consecutive set, then over all runs."""
    values = [r["numbers"][name] for r in runs if name in r["numbers"]]
    size = -(-len(values) // sets)
    parts = [values[i * size:(i + 1) * size] for i in range(sets)]
    return [spread(p) for p in parts + [values] if len(p) >= 2], values


#: the bounds the rule may give, and so the widest spread it admits
BOUNDS = (0.04, 0.06, 0.08, 0.10)


def choose(runs, names, sets: int):
    """``(name, bound, widest spread)`` by the rule above, or None where no
    statistic is admitted."""
    for name in names:
        widest = max(spreads(runs, name, sets)[0])
        fits = [b for b in BOUNDS if 2 * widest < b]
        if fits:
            return name, fits[0], widest
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--choose", nargs="+", default=None)
    args = ap.parse_args(argv)
    runs = []
    for path in args.files:
        with open(path) as f:
            runs.extend(runs_of(f.read()))
    print(f"{len(runs)} runs; correct: {[r['correct'] for r in runs]}; "
          f"failed: {[r['failed'] for r in runs]}")
    names = []
    for r in runs:
        names.extend(n for n in r["numbers"] if n not in names)
    for name in names:
        each, values = spreads(runs, name, args.sets)
        if len(values) < 2:
            continue
        print(f"{name:28s} n={len(values)} median="
              f"{statistics.median(values):.6g} spreads "
              + " ".join(f"{s:.4%}" for s in each)
              + f" min={min(values):.6g} max={max(values):.6g}")
    if args.choose:
        picked = choose(runs, args.choose, args.sets)
        print("chosen:", None if picked is None else
              f"{picked[0]} bound {picked[1]} (widest spread {picked[2]:.4%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
