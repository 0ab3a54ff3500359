"""Device milliseconds a step in operations under a Gated DeltaNet module
(``text.models.GatedDeltaNet``), forward, recomputed forward and backward:
its two fused projections and their split, the one short convolution over
q | k | v, the L2 norms, the decay and beta, q and k repeated to the value
heads, the scan, the gated output norm and the output projection (traced
slice, one device). None for a model without one."""
from benchmark.harness import program_trace

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: any(cls == "GatedDeltaNet"
                                  for _, cls in scope["modules"])) or None
