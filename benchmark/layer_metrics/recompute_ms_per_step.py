"""Device milliseconds a step in the rematerialised forward: operations
whose ``op_name`` carries jax's ``rematted_computation`` scope, which
``jax.checkpoint`` (``fleet.utils.recompute``) puts on the second forward
of a block inside the backward pass (traced slice, one device). It is what
recomputation costs in time for the activations it does not keep; the
model's FLOPs (``train_mfu_pct``) do not count it. None where nothing is
recomputed."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "rematted_computation"


def read(record):
    return _op_names.union_ms_per_step(
        record, lambda name, op: SCOPE in op.split("/"))
