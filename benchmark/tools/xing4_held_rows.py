#!/usr/bin/env python3
"""How many (token, choice) pairs land on the xing4.0-29b-a4b cell's 8 held
experts of 64, a block and a seed, at published widths on the chip: the
reading ``held_rows_factor`` is set from (``configs/xing4.0-29b-a4b.json``
``cut.held_rows``). ``qwen3_next_held_rows.py``'s reading — the framework's
own forward under amp O1 on one row of the cell's traffic, the router's
choices counted where the layer makes them — on this configuration's five
expert blocks (four layers and the MTP module's). Exits 2 without a TPU.

    chiprun -- python3 benchmark/tools/xing4_held_rows.py [first-seed] [seeds]
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402

if __name__ == "__main__":
    tool = cells.load_module("tools", "qwen3_next_held_rows")
    tool.CONFIG, tool.TRAFFIC = "xing4.0-29b-a4b", "lm-s4096-b1-mhc"
    sys.exit(tool.main())
