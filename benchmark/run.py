#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json ``command``):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it holds the cell's chips and fails (non-zero, nothing
printed as a result) without a TPU, on a ``device_kind`` outside
``harness/peaks.py``, with fewer chips than the cell asks for, or in a
directory that does not hold the program. The last line of standard output
is the contract's JSON object; earlier lines are notes, one JSON object
each. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics (and ``device.busy_s``/``window_s``, ``breakdown``).
``setup_s`` runs from the start of this process to the start of the window.
"""
import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, contract, runner, xplane  # noqa: E402


def end_to_end(ctx, bench, cell, result, setup_s):
    values = dict(result["end_to_end"], setup_s=setup_s)
    wanted = [m["name"] for m in cells.metrics_of(bench, "end_to_end", cell)]
    missing = [n for n in wanted if n not in values]
    if missing:
        ctx.log({"error": f"the driver reported no {missing}"})
    return {n: values[n] for n in wanted if n in values}, not missing


def per_layer(ctx, bench, cell, record):
    """Each of the cell's per-layer metrics from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    values = {}
    for m in cells.metrics_of(bench, "per_layer", cell):
        value = cells.load_module("layer_metrics", m["name"]).read(record)
        if value is not None:
            values[m["name"]] = value
    trace = record.get("trace")
    busy = xplane.busy(trace) if trace else None
    if not busy or busy["busy_s"] <= 0:
        ctx.log({"error": "the traced slice holds no device operation"})
        return values, {"busy_s": 0.0, "window_s": 0.0}, None, False
    breakdown = {"device_ops": xplane.top_ops(trace),
                 "idle_gaps": xplane.idle_gaps(trace)}
    with open(os.path.join(ctx.out_dir, "trace_excerpt.json"), "w") as f:
        json.dump(xplane.excerpt(trace), f)
    ctx.log({"trace_lines": [[p["name"], [[ln["name"], len(ln["events"])]
                                          for ln in p["lines"]]]
                             for p in trace["planes"]]})
    return (values, {"busy_s": busy["busy_s"], "window_s": busy["window_s"]},
            breakdown, True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--append", default=None,
                    help="entries to append to BENCHMARK.json's lists for "
                    "this run, relative to the checkout (benchmark/proposed/"
                    "*.json: cells that are built and not yet admitted)")
    args = ap.parse_args()

    bench = cells.load_benchmark(append=args.append)
    cell = cells.find_cell(bench, args.workload)
    ctx = runner.make_context(bench, cell, args.seed, args.seconds,
                              args.trace)
    driver = cells.load_module("drivers", ctx.traffic["driver"])
    result = driver.run(ctx)
    setup_s = result["t_window_start"] - T_PROCESS_START

    device_extra, breakdown = {}, None
    if args.trace:
        group = "per_layer"
        record = result["record"]
        record.update(cell=cell, sizes=ctx.sizes, traffic=ctx.traffic,
                      peaks=ctx.peaks, setup_s=setup_s)
        values, device_extra, breakdown, ok = per_layer(ctx, bench, cell,
                                                        record)
    else:
        group = "end_to_end"
        values, ok = end_to_end(ctx, bench, cell, result, setup_s)
    units = {m["name"]: m["unit"] for m in bench[group]}

    runner.stop_children()
    print(contract.result_line(
        result["correct"] and ok, result["attempted"], result["failed"],
        values, units,
        contract.device_record(ctx.devices, result["memory_peak_bytes"],
                               **device_extra),
        breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
