"""Device milliseconds a step under the expert layers' ``moe.shared`` scope:
the shared expert every token takes, on the hidden-wide stream (its
matrices, the activation between them and the sum with the routed part);
forward, recomputed forward and backward, every expert layer.
``moe_ms_per_step`` leaves it out: its matrices are ``Linear`` modules
inside the layer. Traced slice, one device; None for a program without
one."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "moe.shared"


def read(record):
    return _op_names.union_ms_per_step(
        record, lambda name, op: SCOPE in _op_names.scopes(op))
