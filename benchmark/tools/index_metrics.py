#!/usr/bin/env python3
"""Bring BENCHMARK.json's index up to date: a cell says which metrics it
reports (``reports`` in its traffic file); the driver reads that from the
``workloads`` list on each metric's entry. This appends every missing cell
name to those lists and changes nothing else. ``--check`` only lists them.

    python3 benchmark/tools/index_metrics.py [--check]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402


def main():
    bench = cells.load_benchmark()
    gaps = cells.index_gaps(bench)
    for metric, cell in gaps:
        print(f"{metric}: + {cell}")
    if "--check" in sys.argv[1:]:
        return 1 if gaps else 0
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for metric, cell in gaps:
        by_name[metric]["workloads"].append(cell)
    if gaps:
        with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
