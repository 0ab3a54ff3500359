#!/usr/bin/env python3
"""The nemotron-3-super-120b-a12b configuration's reference check alone, at
published widths on the chip, over a few seeds, with the readings that set
its tolerances (PERF.md section 6, PR 51):
``configs/nemotron-3-super-120b-a12b.py check_train`` (float32 at logit
level over the whole model, amp O1 layer by layer, both loss terms both
ways, the held share's overflow; one row of 4,096 tokens, the model in
pieces), and the same float32 reference computed at the TPU's DEFAULT
matmul precision, which has to come out as not correct by the float32
logits' bound. Exits 2 without a TPU, 1 if a seed is not correct or the
lower-precision reference passes.

    chiprun -- python3 benchmark/tools/nemotron_check.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import field_shapes  # noqa: E402

CONFIG, TRAFFIC = "nemotron-3-super-120b-a12b", "lm-s4096-b1-latentmoe"


def default_precision(config, reference, sizes, built, x):
    """The reference itself one precision down (the platform's default)
    against the reference at "highest", by the check's own rule: the worst
    decided token of the clean prefix, the median token, both loss terms."""
    import numpy as np

    weights = config.Weights(built["layer"], next(iter(x.devices())))
    (exact, lower), margins, _ = config.in_pieces(
        [config.reference_pieces(reference, sizes),
         config.reference_pieces(reference, sizes, None)],
        weights, sizes, x)
    margin = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)
    # the second side is a reference: its layers' third output is dropped
    errs = np.maximum.reduce(
        [config.token_errors(exact[0], lower[0])]
        + [config.token_errors(r, g) for r, g in zip(exact[1], lower[1])])
    decided = margin >= config.F32_MARGIN
    compared = decided & config.clean_prefix(errs, decided, config.F32_RTOL)
    below = {"logits_rel_err": float(errs[compared].max()) if compared.any()
             else float("inf"),
             "logits_rel_err_median": float(np.median(errs)),
             "compared_share": float(compared.mean()),
             "loss_rel_err": abs(float(lower[2]) - float(exact[2]))
             / abs(float(exact[2]))}
    below["fails_f32_logits"] = bool(
        below["logits_rel_err"] > config.F32_RTOL
        or below["logits_rel_err_median"] > config.F32_RTOL)
    below["fails_f32_loss"] = below["loss_rel_err"] > config.LOSS_F32_RTOL
    return below


def main(first, seeds):
    granite = cells.load_module("tools", "granite_check")
    bench = cells.load_benchmark()
    sizes = cells.config_sizes(bench, CONFIG)
    config = cells.load_module("configs", CONFIG)
    reference = cells.load_module("references", CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    shapes = field_shapes(traffic)
    ok = True
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        x = granite.probe_rows(traffic, sizes, seed)
        check = config.check_train(built, reference, sizes, shapes, x)
        below = default_precision(config, reference, sizes, built, x)
        print(json.dumps({"seed": seed, "check": check,
                          "reference_default_precision": below}), flush=True)
        ok = ok and check["ok"] and below["fails_f32_logits"]
        del built
    return 0 if ok else 1


if __name__ == "__main__":
    import jax

    if jax.devices()[0].platform != "tpu":
        print("nemotron_check.py reads the chip's arithmetic: no TPU",
              file=sys.stderr)
        sys.exit(2)
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = sys.argv[1:]
    sys.exit(main(int(args[0]) if args else 2147483401,
                  int(args[1]) if len(args) > 1 else 2))
