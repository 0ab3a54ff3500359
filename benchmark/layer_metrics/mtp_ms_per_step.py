"""Device milliseconds a step in operations under a multi-token-prediction
module (``text.models.MultiTokenPredictor``): its two norms, the 2h -> h
projection, its decoder block (latent attention and the expert layer) and
its norm before the shared head; forward, recomputed forward and backward
(traced slice, one device). The MTP head's loss runs under the step's
``loss`` scope with the main head's (``lm_head_ms_per_step``). None for a
model without one."""
from benchmark.harness import program_trace

LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: any(cls == "MultiTokenPredictor"
                                  for _, cls in scope["modules"])) or None
