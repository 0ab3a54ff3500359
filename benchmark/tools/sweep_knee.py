#!/usr/bin/env python3
"""Find the knee of a serving mix once, on the chip: one process, one
set-up, then a window at every rate of a x1.25 ladder. The knee is the
highest rate at which the 95th percentile of first reply is within the
mix's latency limit, nothing failed or was shed, and completions keep up
with arrivals (>= 0.98 of those offered). Prints one JSON line per rate and
a last line with the knee; PERF.md keeps the table, and 0.8 x the knee goes
into the traffic file as a number.

    python3 benchmark/tools/sweep_knee.py --config bert-base \\
        --traffic embed-open-r80 --first-rate 100 --steps 10 --seconds 8
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, runner  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--first-rate", type=float, default=100.0)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--factor", type=float, default=1.25)
    args = ap.parse_args()

    bench = cells.load_benchmark()
    cell = {"name": f"{args.config}.sweep.{args.traffic}",
            "config": args.config, "traffic": args.traffic,
            "chips": args.chips}
    ctx = runner.make_context(bench, cell, args.seed, args.seconds, 0)
    driver = cells.load_module("drivers", ctx.traffic["driver"])
    handle = driver.setup(ctx)
    knee, rate = None, args.first_rate
    try:
        for _ in range(args.steps):
            window = driver.offer(ctx, handle, rate, args.seconds)
            s = driver.summarise(ctx.traffic, handle["seq"], window)
            shed = (window["counters_after"]["engine_shed"]
                    - window["counters_before"]["engine_shed"])
            row = {k: v for k, v in s.items() if not hasattr(v, "shape")}
            row.update(rate_per_s=rate, shed=shed,
                       window_compiles=window["window_compiles"])
            meets = (s["first_reply_ms_p95"] <= ctx.traffic["latency_limit_ms"]
                     and s["failed"] == 0 and shed == 0
                     and s["completed_in_window_share"] >= 0.98)
            row["meets"] = bool(meets)
            print(json.dumps(row), flush=True)
            if meets:
                knee = rate
            rate *= args.factor
    finally:
        handle["server"].stop()
        runner.stop_children()
    print(json.dumps({"knee_per_s": knee,
                      "device_kind": ctx.devices[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
