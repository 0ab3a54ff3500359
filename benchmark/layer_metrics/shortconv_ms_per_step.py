"""Device milliseconds a step in operations under a gated short-convolution
mixer (``text.models.Lfm2ShortConv``: its scopes start ``shortconv.``),
forward, recomputed forward and backward: the hidden -> 3 x hidden
projection (``shortconv.in_proj``), the gate, the taps and the second gate
(``shortconv.stage``, with the float32 its backward rebuilds) and the
output projection (``shortconv.out_proj``). Traced slice, one device; None
for a program without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_swa = cells.load_module("layer_metrics", "swa_ms_per_step")

LAYER = "short convolution (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "shortconv."


def read(record):
    return _op_names.union_ms_per_step(record, _swa.under(PREFIX))
