"""Device milliseconds a step in operations under a gated grouped-query
attention module (``text.models.GatedGQAttention``), forward, recomputed
forward and backward: the q (query | gate), k, v and o projections, the two
zero-centred QK-norms, the partial RoPE, K and V repeated to the query
heads (scope ``gqa.repeat``), the streaming kernel's calls, the gate and
the head merge (traced slice, one device). None for a model without one."""
from benchmark.harness import program_trace

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: any(cls == "GatedGQAttention"
                                  for _, cls in scope["modules"])) or None
