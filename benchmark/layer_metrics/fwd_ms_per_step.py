"""Device milliseconds a step in the forward pass: the union of the
intervals of operations under a module or ``loss`` scope that jax did not
wrap in ``transpose(`` (traced slice, one device). A fusion counts where
its root is."""
from benchmark.harness import program_trace

LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: scope["phase"] == "fwd")
