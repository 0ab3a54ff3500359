#!/usr/bin/env python3
"""Medians and spreads of the sets ``chip_sets.sh`` ran: for every metric
and set the median and the spread (distance between the quartiles over the
median), as the driver reads them. No jax, no chip."""
import json
import sys

import numpy as np


def main(path):
    sets = {}
    with open(path) as f:
        for raw in f:
            row = json.loads(raw)
            line = row["line"]
            if row["rc"] != 0 or not line.get("correct"):
                print(json.dumps({"bad_run": row})[:2000])
            if row["set"] == "trace":
                print(json.dumps({"traced": line})[:6000])
                continue
            for name, m in line["metrics"].items():
                sets.setdefault(name, {}).setdefault(row["set"], []).append(
                    m["value"])
    for name, by_set in sets.items():
        for k, values in sorted(by_set.items()):
            v = np.asarray(values)
            q1, med, q3 = np.percentile(v, [25, 50, 75])
            print(json.dumps({"metric": name, "set": k, "n": len(v),
                              "median": med, "q1": q1, "q3": q3,
                              "spread": (q3 - q1) / med,
                              "values": values}))


if __name__ == "__main__":
    main(sys.argv[1])
