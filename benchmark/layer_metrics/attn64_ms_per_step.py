"""Device milliseconds a step in operations under LFM2's grouped-query
attention module (``text.models.Lfm2Attention``: its scopes start
``lfm2attn.``), forward, recomputed forward and backward: the q, k and v
projections (``lfm2attn.proj``), the two 64-wide QK-norms, RoPE and the
head split (``.qk``), K and V repeated from 8 to the 32 query heads
(``.repeat``), the streaming kernel's calls at heads of 64 (``.core``), the
head merge and ``out_proj`` (``.out``). Traced slice, one device; None for
a program without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_swa = cells.load_module("layer_metrics", "swa_ms_per_step")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "lfm2attn."


def read(record):
    return _op_names.union_ms_per_step(record, _swa.under(PREFIX))
