"""Mean enqueue-to-dispatch wait of a request in the batching engine over
the window (``paddle_serving_queue_wait_seconds`` sum/count deltas)."""
from benchmark.harness import cells

LAYER = "serving engine (inference/batching.py, server.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "first_reply_ms_p95"


def read(record):
    s = cells.load_module("layer_metrics", "_serving")
    return s.ratio(record, "paddle_serving_queue_wait_seconds_sum",
                   "paddle_serving_queue_wait_seconds_count", 1e3)
