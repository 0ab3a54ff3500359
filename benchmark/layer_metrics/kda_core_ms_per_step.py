"""Device milliseconds a step under Kimi Delta Attention's ``kda.core``
scope: the gated delta rule's scan and nothing else (the chunk's decay sums
and pair terms, the inverse, the loop over chunks, the outputs; forward,
the block's recomputed forward, backward with the segments it rebuilds).
The rest of ``kda_ms_per_step`` is projections, convolutions and gates.
Traced slice, one device; None for a model without the layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "kda.core"


def in_core(name, op):
    return SCOPE in _op_names.scopes(op)


def read(record):
    return _op_names.union_ms_per_step(record, in_core)
