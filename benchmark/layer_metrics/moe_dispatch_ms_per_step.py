"""Of the expert layer's device time, the milliseconds a step under its
``moe.route``, ``moe.dispatch`` and ``moe.combine`` scopes: the router, the
sort, the row gathers into expert order and back, the weighted sum over a
token's choices — everything that is not an expert gemm (which runs under
``moe.experts``). Forward and backward; traced slice, one device."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

AROUND_THE_GEMMS = {"moe.route", "moe.dispatch", "moe.combine"}


def read(record):
    return _op_names.union_ms_per_step(
        record, lambda name, op: AROUND_THE_GEMMS & set(_op_names.scopes(op)))
