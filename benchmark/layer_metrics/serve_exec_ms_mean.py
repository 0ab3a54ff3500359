"""Mean execute time of a batch (one bucket program, with its transfer in
and out) over the window (``paddle_serving_batch_exec_seconds`` sum/count
deltas)."""
from benchmark.harness import cells

LAYER = "bucket programs (AotLayerRunner)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "first_reply_ms_p50"


def read(record):
    s = cells.load_module("layer_metrics", "_serving")
    return s.ratio(record, "paddle_serving_batch_exec_seconds_sum",
                   "paddle_serving_batch_exec_seconds_count", 1e3)
