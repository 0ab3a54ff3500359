"""Device milliseconds a step in operations under a state-space mixer
(``text.models.Mamba2Mixer``: its scopes start ``mamba.``), forward,
recomputed forward and backward, all nine layers: the hidden -> [z | xBC |
dt] projection and its split (``mamba.in_proj``), taps, bias, SiLU and the
x | B | C split (``.conv``), the step's softplus (``.dt``), the scan with
its ``D`` skip (``.core``), the gate-first norm (``.norm``) and the output
projection (``.out_proj``). Traced slice, one device; None for a program
without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_swa = cells.load_module("layer_metrics", "swa_ms_per_step")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "mamba."


def read(record):
    return _op_names.union_ms_per_step(record, _swa.under(PREFIX))
