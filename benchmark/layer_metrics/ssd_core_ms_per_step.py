"""Device milliseconds a step under the state-space mixers' ``mamba.core``
scope: the selective state-space scan and nothing else (``dt x``, the
decay's sums, the group's pair product under each head's mask, the states
between chunks, the outputs, the ``D`` skip; forward, the block's recomputed
forward, backward with the segments it rebuilds). The rest of
``mamba_ms_per_step`` is projections, the convolution stage and the norm.
Traced slice, one device; None for a model without the layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "mamba.core"


def in_core(name, op):
    return SCOPE in _op_names.scopes(op)


def read(record):
    return _op_names.union_ms_per_step(record, in_core)
