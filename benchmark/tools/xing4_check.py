#!/usr/bin/env python3
"""The xing4.0-29b-a4b configuration's reference check alone, at published
widths on the chip, over a few seeds, with the readings that set its
tolerances (PERF.md section 6, PR 53):
``configs/xing4.0-29b-a4b.py check_train`` (float32 at logit level over the
whole model, amp O1 block by block on the streams, both loss terms both
ways, the held share's overflow; one row of 4,096 tokens, the model in
pieces). On the first seed the same check runs three times more with the
REFERENCE built otherwise — at the TPU's DEFAULT matmul precision, with 19
Sinkhorn rounds, with H_res left unprojected (no round) — and each has to
come out as not correct. Exits 2 without a TPU, 1 if a seed is not correct
or a broken reference passes.

    chiprun -- python3 benchmark/tools/xing4_check.py [first-seed] [seeds]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import field_shapes  # noqa: E402

CONFIG, TRAFFIC = "xing4.0-29b-a4b", "lm-s4096-b1-mhc"

#: the reference built otherwise -> check_train's arguments
BROKEN = {
    "reference_default_precision": {"reference_precision": None},
    "reference_19_rounds": {"reference_sizes": {"hc_sinkhorn_iters": 19}},
    "reference_unprojected": {"reference_sizes": {"hc_sinkhorn_iters": 0}},
}
#: what each reading says of a check
KEPT = ("ok", "f32_rel_err", "f32_rel_err_median", "f32_compared_share",
        "amp_rel_err", "amp_block_worst", "amp_block_medians",
        "loss_f32_rel_err", "loss_amp_rel_err")


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
        out = {"seed": seed, "check": check}
        ok = ok and check["ok"]
        if seed == first:
            for name, how in BROKEN.items():
                if "reference_sizes" in how:
                    how = {"reference_sizes": dict(sizes,
                                                   **how["reference_sizes"])}
                broken = config.check_train(built, reference, sizes, shapes,
                                            x, **how)
                out[name] = {k: broken[k] for k in KEPT}
                ok = ok and not broken["ok"]
        print(json.dumps(out), flush=True)
        del built
    return 0 if ok else 1


if __name__ == "__main__":
    import jax

    if jax.devices()[0].platform != "tpu":
        print("xing4_check.py reads the chip's arithmetic: no TPU",
              file=sys.stderr)
        sys.exit(2)
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = sys.argv[1:]
    sys.exit(main(int(args[0]) if args else 2147483501,
                  int(args[1]) if len(args) > 1 else 2))
