"""Mean real rows in an executed batch over the window
(``paddle_serving_batch_rows_total`` / ``paddle_serving_batches_total``)."""
from benchmark.harness import cells

LAYER = "serving engine (inference/batching.py, server.py)"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "first_reply_ms_p50"


def read(record):
    s = cells.load_module("layer_metrics", "_serving")
    return s.ratio(record, "paddle_serving_batch_rows_total",
                   "paddle_serving_batches_total")
