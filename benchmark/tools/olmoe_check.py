#!/usr/bin/env python3
"""The OLMoE configuration's reference check alone, at published widths on
the chip, over a few seeds, with the reading that sets its tolerances
(PERF.md section 6, PR 25): the framework model against the float32
reference in float32 and under amp O1 (``configs/olmoe-1b-7b.py
check_train``: logits and loss on one 4,096-token row), and the same
reference computed at the TPU's DEFAULT matmul precision (bf16 passes) —
the nearest precision below the float32 the check states, which has to
come out as not correct. No train state is built, so it is cheaper than a
run of the cell. Exits 2 without a TPU, 1 if a seed is not correct or the
lower-precision reference passes.

    chiprun -- python3 benchmark/tools/olmoe_check.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "olmoe-1b-7b", "lm-s4096-b4"


def main():
    import jax
    import numpy as np

    first = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483001
    seeds = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    if jax.devices()[0].platform != "tpu":
        print("olmoe_check.py reads the chip's arithmetic: no TPU",
              file=sys.stderr)
        return 2
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    bench = cells.load_benchmark()
    sizes = cells.config_sizes(bench, CONFIG)
    config = cells.load_module("configs", CONFIG)
    reference = cells.load_module("references", CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    shapes = field_shapes(traffic)
    device = jax.devices()[0]
    ok = True
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        x = jax.device_put(
            SeededDataset(traffic, sizes, seed, 1)[0][0][None], device)
        check = config.check_train(built, reference, sizes, shapes, x)

        # the reference itself one precision down: the platform's default
        params = jax.device_put(built["layer"].functional_state()[0], device)

        exact = jax.jit(config.reference_outputs(reference, sizes))(params, x)
        lower = jax.jit(config.reference_outputs(reference, sizes, None))(
            params, x)
        below = config.compare(exact, lower, lower)
        # the reading AMP_MARGIN and F32_MARGIN are set from: the worst
        # token among those whose router margin is at least m, and the
        # share of tokens under m
        margin = np.asarray(exact[3])
        with jax.default_matmul_precision("highest"):
            got32 = jax.jit(lambda p, a: config._framework(
                built["layer"], p, None, a))(params, x)
        got_amp = jax.jit(lambda p, a: config._framework(
            built["layer"], p, built["amp_level"], a))(params, x)
        by_margin = []
        for m in (0.0, 1e-5, 1e-4, 1e-3, 3e-3, 1e-2, 2e-2, 3e-2, 1e-1):
            keep = margin >= m
            by_margin.append({"margin": m, "share_under": float(
                1 - keep.mean())} | {tag: float(config.token_errors(
                    exact, got)[keep].max()) for tag, got in (
                        ("f32", got32), ("amp", got_amp),
                        ("reference_default_precision", lower))})
        router_shift = float(np.abs(np.asarray(lower[3]) - margin).max())
        line = {"seed": seed, "check": check, "reference_default_precision": {
            "logits_rel_err": below["f32_rel_err"],
            "logits_rel_err_all_tokens": below["f32_rel_err_all_tokens"],
            "loss_rel_err": below["loss_f32_rel_err"],
            "margin_shift_max": router_shift,
            "fails_f32_logits": below["f32_rel_err"] > config.F32_RTOL,
            "fails_f32_loss": below["loss_f32_rel_err"]
            > config.LOSS_F32_RTOL}, "worst_token_by_margin": by_margin}
        print(json.dumps(line), flush=True)
        ok = ok and check["ok"] and below["f32_rel_err"] > config.F32_RTOL
        del built, params, exact, lower
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
