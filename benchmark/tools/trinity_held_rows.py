#!/usr/bin/env python3
"""How many (token, choice) pairs land on the Trinity-Mini cell's 16 held
experts, a layer and a seed, at published widths on the chip: the reading
``held_rows_factor`` is set from (``configs/trinity-mini.json``
``cut.held_rows``). It is ``qwen3_next_held_rows.py``'s survey — the
framework's own forward under amp O1 on one 16,384-token row of the cell's
traffic, the router's choices counted where the layer makes them — on this
configuration and its traffic. Exits 2 without a TPU.

    chiprun -- python3 benchmark/tools/trinity_held_rows.py [first-seed] [seeds]
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402

if __name__ == "__main__":
    survey = cells.load_module("tools", "qwen3_next_held_rows")
    survey.CONFIG, survey.TRAFFIC = "trinity-mini", "lm-s16384-b1-swa"
    sys.exit(survey.main())
