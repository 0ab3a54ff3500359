"""Device milliseconds a step in operations under a FULL-attention module
without positions (``text.models.AfmoeAttention`` of a ``full_attention``
layer: its scopes start ``gattn.``), forward, recomputed forward and
backward: ``swa_ms_per_step``'s stages without RoPE, the core the streaming
kernel's full-causal calls. Traced slice, one device; None for a program
without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_swa = cells.load_module("layer_metrics", "swa_ms_per_step")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "gattn."


def read(record):
    return _op_names.union_ms_per_step(record, _swa.under(PREFIX))
