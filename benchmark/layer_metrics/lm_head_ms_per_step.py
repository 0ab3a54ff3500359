"""Device milliseconds a step under the step program's ``loss`` scope or a
``lm_head`` module: for a language model whose loss takes the head's weight
(``F.linear_cross_entropy``) that is the head's three gemms, the softmax
over the vocabulary and the auxiliary losses' sum. It is the part of a step
a depth cut inflates (one layer keeps the whole head), so a reader can take
it out. Traced slice, one device."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def _head_or_loss(name, op):
    parts = _op_names.scopes(op)
    return "loss" in parts or any(p.startswith("lm_head:") for p in parts)


def read(record):
    return _op_names.union_ms_per_step(record, _head_or_loss)
