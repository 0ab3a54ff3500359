"""Share of executed rows that were padding over the window
(``paddle_serving_padded_rows_total`` over real + padded rows)."""
from benchmark.harness import cells

LAYER = "serving engine (inference/batching.py, server.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_good_tokens_per_s"


def read(record):
    s = cells.load_module("layer_metrics", "_serving")
    pad = s.delta(record, "paddle_serving_padded_rows_total")
    real = s.delta(record, "paddle_serving_batch_rows_total")
    if pad is None or real is None or pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
