#!/usr/bin/env python3
"""The Trinity-Mini configuration's reference check alone, at published
widths on the chip, over a few seeds, with the readings that set its
tolerances (PERF.md section 6, PR 40): ``configs/trinity-mini.py
check_train`` (float32 at logit level over the whole model, amp O1 block by
block, the loss, the overflow count and the pairs that landed on the held
experts a layer; one 16,384-token row), and the same float32 reference
computed at the TPU's DEFAULT matmul precision, which has to come out as not
correct by the float32 logits' bound. It is ``kimi_check.py``'s procedure on
this configuration and its traffic. Exits 2 without a TPU, 1 if a seed is
not correct or the lower-precision reference passes.

    chiprun -- python3 benchmark/tools/trinity_check.py [first-seed] [seeds]

With ``--window-off`` instead: the program built with a sliding window one
kernel block (1,024 keys) SHORT of the configuration's, checked against the
reference at the configuration's window. The block-by-block half has to
fail on the sliding blocks and pass on the full one, whose mask no window
touches. Exits 1 if that program comes out correct.

    chiprun -- python3 benchmark/tools/trinity_check.py --window-off [seed]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "trinity-mini", "lm-s16384-b1-swa"
#: the banded kernel's block at the cell's shape (``_stream_block``: d 128
#: in bf16)
WINDOW_OFF_BY = 1024


def window_off(seed):
    import jax

    if jax.devices()[0].platform != "tpu":
        print("trinity_check.py reads the chip's arithmetic: no TPU",
              file=sys.stderr)
        return 2
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    sizes = cells.config_sizes(cells.load_benchmark(), CONFIG)
    config = cells.load_module("configs", CONFIG)
    reference = cells.load_module("references", CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    shapes = field_shapes(traffic)
    short = dict(sizes, sliding_window=sizes["sliding_window"]
                 - WINDOW_OFF_BY)
    built = config.build_train(seed, short, shapes)
    x = jax.device_put(SeededDataset(traffic, sizes, seed, 1)[0][0][None],
                       jax.devices()[0])
    check = config.check_train(built, reference, sizes, shapes, x)
    keep = ("ok", "amp_rel_err", "amp_rtol", "amp_block_medians",
            "f32_rel_err", "f32_rtol", "f32_rel_err_median",
            "f32_median_rtol")
    print(json.dumps({"seed": seed,
                      "program_window": short["sliding_window"],
                      "reference_window": sizes["sliding_window"],
                      "layer_types": config.layer_types(sizes),
                      **{k: check[k] for k in keep}}), flush=True)
    fails = (not check["ok"] and check["amp_rel_err"] > check["amp_rtol"]
             and check["f32_rel_err_median"] > check["f32_median_rtol"])
    return 0 if fails else 1


if __name__ == "__main__":
    if sys.argv[1:2] == ["--window-off"]:
        sys.exit(window_off(int(sys.argv[2]) if len(sys.argv) > 2
                            else 2147483201))
    check = cells.load_module("tools", "kimi_check")
    check.CONFIG, check.TRAFFIC = CONFIG, TRAFFIC
    sys.exit(check.main())
