"""Device milliseconds a step in operations whose innermost module scope
is a ``LayerNorm`` or a ``BatchNorm*``, forward and backward (traced slice,
one device). A fusion counts where its root is: the part of a norm that XLA
fuses behind a neighbouring gemm or convolution counts there, not here."""
from benchmark.harness import program_trace

LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: program_trace.innermost(scope).startswith(
            ("LayerNorm", "BatchNorm")))
