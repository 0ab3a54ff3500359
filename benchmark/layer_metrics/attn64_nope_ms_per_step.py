"""Device milliseconds a step in operations under Granite-4.0-H's attention
module (``text.models.GraniteAttention``: its scopes start ``gattn64.``),
forward, recomputed forward and backward: the q, k and v projections and
the head split (``gattn64.proj``), K and V repeated from 8 to the 32 query
heads (``.repeat``), the streaming kernel's calls at heads of 64 and a scale
of 1/64 (``.core``), the head merge and ``o_proj`` (``.out``); nothing
rotated, no norm a head. Traced slice, one device; None for a program
without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_swa = cells.load_module("layer_metrics", "swa_ms_per_step")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "gattn64."


def read(record):
    return _op_names.union_ms_per_step(record, _swa.under(PREFIX))
