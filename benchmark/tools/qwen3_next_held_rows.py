#!/usr/bin/env python3
"""How many (token, choice) pairs land on the Qwen3-Next cell's 32 held
experts, a layer and a seed, at published widths on the chip: the reading
``held_rows_factor`` is set from (``configs/qwen3-next-80b-a3b.json``
``cut.held_rows``). The framework's own forward under amp O1 on one
16,384-token row of the cell's traffic, the router's choices counted where
the layer makes them; one compiled program for all seeds. Exits 2 without a
TPU.

    chiprun -- python3 benchmark/tools/qwen3_next_held_rows.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "qwen3-next-80b-a3b", "lm-s16384-b1-gdn"


def landed_fn(built, sizes):
    """(params, buffers, ids) -> the pairs that land on the held experts in
    each layer, counted from the router's own choices in the framework's
    forward (eval mode: no recomputation, so the counts leave the trace
    they were made in)."""
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate import moe

    joyai = cells.load_module("configs", "joyai-llm-flash")
    layer = built["layer"]
    first, count = sizes["held_experts"]

    def fn(params, buffers, x):
        counts, route = [], moe._route

        def counting(*args, **kw):
            out = route(*args, **kw)
            counts.append(jnp.sum((out[1] >= first)
                                  & (out[1] < first + count)))
            return out

        moe._route = counting
        try:
            joyai._traced(layer, params, buffers, built["amp_level"],
                          lambda: layer.forward(Tensor(
                              x, stop_gradient=True))[0]._value)
        finally:
            moe._route = route
        return jnp.stack(counts)

    return fn


def main():
    import jax

    first = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483101
    seeds = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    if jax.devices()[0].platform != "tpu":
        print("qwen3_next_held_rows.py reads the chip's router: no TPU",
              file=sys.stderr)
        return 2
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    bench = cells.load_benchmark()
    sizes = cells.config_sizes(bench, CONFIG)
    config = cells.load_module("configs", CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    shapes = field_shapes(traffic)
    device = jax.devices()[0]
    tokens = shapes["input_ids"][0]
    mean = (tokens * sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"])
    run, readings = None, []
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        built["layer"].eval()
        if run is None:
            run = jax.jit(landed_fn(built, sizes))
        params, buffers = jax.device_put(
            built["layer"].functional_state(), device)
        x = jax.device_put(
            SeededDataset(traffic, sizes, seed, 1)[0][0][None], device)
        landed = [int(v) for v in jax.device_get(run(params, buffers, x))]
        readings += landed
        print(json.dumps({"seed": seed, "held_pairs_landed": landed,
                          "over_mean": [round(v / mean, 3)
                                        for v in landed]}), flush=True)
        del built, params, buffers
    print(json.dumps({"mean": mean, "readings": len(readings),
                      "least": min(readings), "most": max(readings),
                      "most_over_mean": max(readings) / mean}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
