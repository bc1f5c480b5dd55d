#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over two sets of runs, and the
bound each spread supports.

    python3 benchmarks/chip/bounds.py --set A1.out ... A6.out \
        --set B1.out ... B6.out

Each file is a run's standard output; its last line is the result.  Per
metric: each set's median and spread (the distance between the first and
third quartile of `statistics.quantiles`, over the median), the widest
spread, and about five times it as the bound, never under 1% nor over
25%.  `setup_s` keeps 25%: only its median is judged.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--set", nargs="+", action="append", required=True)
    args = ap.parse_args(argv)
    sets = [[last_line(p) for p in paths] for paths in args.set]
    out = {}
    for name in sets[0][0]["metrics"]:
        values = [[r["metrics"][name]["value"] for r in runs] for runs in sets]
        spreads = [harness.spread(v) for v in values]
        widest = max(spreads)
        bound = 0.25 if name == "setup_s" else min(0.25, max(0.01, 5 * widest))
        out[name] = {"medians": [statistics.median(v) for v in values],
                     "spreads": spreads, "widest": widest, "bound": bound}
        print(json.dumps({name: out[name]}))
    return out


if __name__ == "__main__":
    main()
