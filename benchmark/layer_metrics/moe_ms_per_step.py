"""Device milliseconds a step in operations whose innermost module scope is
the expert layer (``incubate.moe.MoELayer``), forward and backward: router,
sort and gathers, the grouped expert matmuls with SwiGLU, the weighted
un-sort (traced slice, one device). None for a model without one."""
from benchmark.harness import program_trace

LAYER = "expert layer (incubate/moe.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: program_trace.innermost(scope) == "MoELayer"
    ) or None
