#!/usr/bin/env python3
"""The Kimi-Linear configuration's reference check alone, at published
widths on the chip, over a few seeds, with the readings that set its
tolerances (PERF.md section 6, PR 32): ``configs/kimi-linear-48b-a3b.py
check_train`` (float32 at logit level over the whole model, amp O1 block by
block, the loss, the overflow count; one 16,384-token row), and the same
float32 reference computed at the TPU's DEFAULT matmul precision
(bf16 passes) — the nearest precision below the float32 the check states,
which has to come out as not correct by the float32 logits' bound. Also
printed: the worst token by router margin, which AMP_MARGIN and F32_MARGIN
are read from. No train state is built, so it is cheaper than a run of the
cell. Exits 2 without a TPU, 1 if a seed is not correct or the
lower-precision reference passes.

    chiprun -- python3 benchmark/tools/kimi_check.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "kimi-linear-48b-a3b", "lm-s16384-b1"
MARGINS = (0.0, 1e-6, 1e-5, 1e-4, 1e-3, 3e-3, 1e-2, 2e-2, 5e-2)


def main():
    import jax
    import numpy as np

    first = int(sys.argv[1]) if len(sys.argv) > 1 else 2147483001
    seeds = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    if jax.devices()[0].platform != "tpu":
        print("kimi_check.py reads the chip's arithmetic: no TPU",
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
        params, buffers = jax.device_put(
            built["layer"].functional_state(), device)
        exact = jax.device_get(jax.jit(config.reference_outputs(
            reference, sizes))(params, buffers, x))
        lower = jax.device_get(jax.jit(config.reference_outputs(
            reference, sizes, None))(params, buffers, x))
        margin = np.asarray(exact[4])
        errs = config.token_errors(exact[0], lower[0])
        by_margin = [{"margin": m, "share_under": float((margin < m).mean()),
                      "reference_default_precision": float(
                          errs[margin >= m].max(initial=0.0))}
                     for m in MARGINS]
        # by the check's own rule: the decided tokens of the clean prefix
        decided = margin >= config.F32_MARGIN
        compared = decided & config.clean_prefix(errs, decided,
                                                 config.F32_RTOL)
        below = {
            "logits_rel_err": float(errs[compared].max()
                                    if compared.any() else np.inf),
            "logits_rel_err_decided": float(errs[decided].max()),
            "compared_share": float(compared.mean()),
            "logits_rel_err_median": float(np.median(errs)),
            "loss_rel_err": abs(float(lower[1]) - float(exact[1]))
            / abs(float(exact[1])),
            "margin_shift_max": float(np.abs(
                np.asarray(lower[4]) - margin).max())}
        below["fails_f32_logits"] = bool(
            below["logits_rel_err"] > config.F32_RTOL
            or below["logits_rel_err_median"] > config.F32_RTOL)
        below["fails_f32_loss"] = below["loss_rel_err"] > config.LOSS_F32_RTOL
        print(json.dumps({"seed": seed, "check": check,
                          "reference_default_precision": below,
                          "worst_token_by_margin": by_margin}), flush=True)
        ok = ok and check["ok"] and below["fails_f32_logits"]
        del built, params, buffers, exact, lower
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
