#!/usr/bin/env python3
"""The Qwen3-Next configuration's reference check alone, at published widths
on the chip, over a few seeds, with the readings that set its tolerances
(PERF.md section 6, PR 38): ``configs/qwen3-next-80b-a3b.py check_train``
(float32 at logit level over the whole model, amp O1 block by block, the
loss, the overflow count and the pairs that landed on the held experts a
layer; one 16,384-token row), and the same float32 reference computed at the
TPU's DEFAULT matmul precision, which has to come out as not correct by the
float32 logits' bound. It is ``kimi_check.py``'s procedure on this
configuration and its traffic. Exits 2 without a TPU, 1 if a seed is not
correct or the lower-precision reference passes.

    chiprun -- python3 benchmark/tools/qwen3_next_check.py [first-seed] [seeds]
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402

if __name__ == "__main__":
    check = cells.load_module("tools", "kimi_check")
    check.CONFIG, check.TRAFFIC = "qwen3-next-80b-a3b", "lm-s16384-b1-gdn"
    sys.exit(check.main())
