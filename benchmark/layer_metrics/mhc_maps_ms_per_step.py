"""Of the residual path's device time (``mhc_ms_per_step``), the
milliseconds a step on its COEFFICIENT side: the operations under
``mhc.maps`` (the streams' RMS norm and the float32 product with ``phi``: a
[tokens, n C] x [n C, 2 n + n^2] gemm whose 24 columns fill a fifth of the
MXU's) and ``mhc.sinkhorn`` (two sigmoids and twenty Sinkhorn-Knopp rounds
on a 4 x 4 matrix a token: 16 numbers a token, so bound by the VPU and by
the launch of each fused piece, not by bytes). Forward, recomputed forward
and backward; traced slice, one device. None for a model without the
streams."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_mhc = cells.load_module("layer_metrics", "mhc_ms_per_step")

LAYER = _mhc.LAYER
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPES = {"mhc.maps", "mhc.sinkhorn"}


def read(record):
    return _op_names.union_ms_per_step(record, _mhc.under(SCOPES))
