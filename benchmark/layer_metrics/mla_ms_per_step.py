"""Device milliseconds a step in operations under a latent-attention module
(``text.models.MLAttention``), forward, recomputed forward and backward:
its seven projections, the two norms, RoPE, the streaming flash kernel's
calls and the head merge (traced slice, one device). None for a model
without one."""
from benchmark.harness import program_trace

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: any(cls == "MLAttention"
                                  for _, cls in scope["modules"])) or None
