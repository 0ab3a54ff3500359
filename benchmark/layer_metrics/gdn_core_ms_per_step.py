"""Device milliseconds a step under Gated DeltaNet's ``gdn.core`` scope: the
gated delta rule's scan with one decay a head and nothing else (the chunk's
decay sums and factored pair terms, the inverse, the loop over chunks, the
outputs; forward, the block's recomputed forward, backward). The rest of
``gdn_ms_per_step`` is projections, the convolution, gates and the repeat of
q and k. Traced slice, one device; None for a model without the layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "gdn.core"


def in_core(name, op):
    return SCOPE in _op_names.scopes(op)


def read(record):
    return _op_names.union_ms_per_step(record, in_core)
