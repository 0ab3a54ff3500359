#!/usr/bin/env python3
"""How many (token, choice) pairs land on the LFM2 cell's 8 held experts, a
layer and a seed, at published widths on the chip: the reading
``held_rows_factor`` is set from (``configs/lfm2-8b-a1b.json``
``cut.held_rows``). It is ``qwen3_next_held_rows.py``'s survey — the
framework's own forward under amp O1, the router's choices counted where
the layer makes them (its ``landed_fn``) — on this configuration and on a
whole STEP of its traffic, 4 rows of 8,192 tokens: the row buffer is a
step's, and a step's 32,768 tokens spread less than one row's would. Exits
2 without a TPU.

    chiprun -- python3 benchmark/tools/lfm2_held_rows.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import field_shapes  # noqa: E402

CONFIG, TRAFFIC = "lfm2-8b-a1b", "lm-s8192-b4-conv"


def main():
    import jax

    first = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483401
    seeds = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    if jax.devices()[0].platform != "tpu":
        print("lfm2_held_rows.py reads the chip's router: no TPU",
              file=sys.stderr)
        return 2
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    survey = cells.load_module("tools", "qwen3_next_held_rows")
    check = cells.load_module("tools", "lfm2_check")
    sizes = cells.config_sizes(cells.load_benchmark(), CONFIG)
    config = cells.load_module("configs", CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    shapes = field_shapes(traffic)
    rows = traffic["rows_per_chip"]
    tokens = rows * shapes["input_ids"][0]
    mean = (tokens * sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"])
    run, readings = None, []
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        built["layer"].eval()
        if run is None:
            run = jax.jit(survey.landed_fn(built, sizes))
        params, buffers = jax.device_put(
            built["layer"].functional_state(), jax.devices()[0])
        x = check.probe_rows(traffic, sizes, seed, rows)
        landed = [int(v) for v in jax.device_get(run(params, buffers, x))]
        readings += landed
        print(json.dumps({"seed": seed, "held_pairs_landed": landed,
                          "over_mean": [round(v / mean, 3)
                                        for v in landed]}), flush=True)
        del built, params, buffers
    print(json.dumps({"mean": mean, "readings": len(readings),
                      "least": min(readings), "most": max(readings),
                      "least_over_mean": min(readings) / mean,
                      "most_over_mean": max(readings) / mean}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
