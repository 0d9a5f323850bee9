#!/usr/bin/env python3
"""Spread of each end-to-end metric over two sets of runs, by the contract's
rule: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the wider
of the two sets; the bound is about five times the widest over the cells.

    python3 benchmark/tests/spread.py chiprun_out/sets/<workload>
"""

from __future__ import annotations

import json
import statistics
import sys


def lines(path):
    out = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if ln.startswith("{"):
                out.append(json.loads(ln))
    return out


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(prefix):
    sets = [lines(f"{prefix}.set{k}.jsonl") for k in (1, 2)]
    first = lines(f"{prefix}.first.jsonl")
    report = {"cell": prefix.rsplit("/", 1)[-1],
              "runs": [len(s) for s in sets],
              "all_correct": all(r["correct"] for s in sets for r in s),
              "failed": sum(r["failed"] for s in sets for r in s),
              "memory_peak_bytes": max(r["device"]["memory_peak_bytes"]
                                       for s in sets for r in s),
              "first_run_setup_s": (first[0]["metrics"]["setup_s"]["value"]
                                    if first else None),
              "metrics": {}}
    for name in sets[0][0]["metrics"]:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        med = [statistics.median(v) for v in vals]
        report["metrics"][name] = {
            "medians": med, "spreads": [spread(v) for v in vals],
            "second_over_first": med[1] / med[0] - 1.0,
            "values": vals}
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
