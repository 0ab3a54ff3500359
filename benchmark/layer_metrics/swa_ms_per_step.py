"""Device milliseconds a step in operations under a SLIDING-WINDOW attention
module (``text.models.AfmoeAttention`` of a ``sliding_attention`` layer: its
scopes start ``swa.``), forward, recomputed forward and backward: the q, k,
v and gate projections (``swa.proj``), the two QK-norms, RoPE and the head
split (``swa.qk``), K and V repeated to the query heads (``swa.repeat``),
the banded kernel's calls (``swa.core``), the gate, the head merge and
``o_proj`` (``swa.out``). Traced slice, one device; None for a program
without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "swa."


def under(prefix):
    """(event name, op_name) -> whether one of the ``op_name``'s scopes
    starts with ``prefix``."""
    return lambda name, op: any(part.startswith(prefix)
                                for part in _op_names.scopes(op))


def read(record):
    return _op_names.union_ms_per_step(record, under(PREFIX))
