"""Device milliseconds a step under a ``MultiHeadAttention`` scope and
under none of its ``Linear`` children, forward and backward: scores,
softmax, probability dropout, context, and whatever the module computes in
its own ``forward`` (today the fused QKV projection, which bypasses
``q_proj``/``k_proj``/``v_proj``). None for a model without attention."""
from benchmark.harness import program_trace

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def _attention(scope):
    classes = [cls for _, cls in scope["modules"]]
    if "MultiHeadAttention" not in classes:
        return False
    return "Linear" not in classes[classes.index("MultiHeadAttention"):]


def read(record):
    return program_trace.union_ms_per_step(record, _attention) or None
