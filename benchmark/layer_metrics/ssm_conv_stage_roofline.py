"""The state-space mixer's convolution stage against its roofline: the
least time the chip could take to move the bytes of the passes THAT RAN —
at the HBM peak of ``harness/peaks.py`` — over the device time of the events
under ``mamba.conv``, as ``shortconv_stage_roofline`` reads LFM2's stage.
The stage is element-wise (4 taps, a bias and SiLU a channel: about 12
FLOPs a channel-token against 4 bytes), so the byte bound is its roofline.

The bytes are the stage's least, from tokens, the x | B | C channels and
the stream's item size (``stage_bytes``): forward xBC read and the
convolved stream written; backward xBC and the cotangent read and one
cotangent written (the convolved stream need not be written again). The
function says nothing of what implements the stage — ``conv_streams``' XLA
operations under a ``jax.checkpoint`` today, float32 arrays and all, the
kernel with a bias later — so the share reads the same work under either.
The passes are ``ssd_core_roofline``'s count, on this scope."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_ssd = cells.load_module("layer_metrics", "ssd_core_roofline")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "mamba.conv"


def stage_bytes(tokens, channels, forwards=1, backwards=0, itemsize=2):
    """Least bytes of ``forwards`` forward and ``backwards`` backward passes
    of the stage over ``tokens`` tokens of ``channels`` channels: forward
    one stream read and one written, backward two read (the cotangent too)
    and one written; taps and bias (a few kB) count for nothing."""
    return float(tokens) * channels * itemsize * (2 * forwards
                                                  + 3 * backwards)


def in_stage(name, op):
    return SCOPE in _op_names.scopes(op)


def read(record):
    sizes = record.get("sizes", {})
    if "mamba_n_heads" not in sizes:
        return None
    ms = _op_names.union_ms_per_step(record, in_stage)
    if not ms:
        return None
    channels = (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
                + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"])
    forwards, backwards = _ssd.passes(record, SCOPE)
    least_s = (stage_bytes(_ssd.mamba_tokens(record), channels, forwards,
                           backwards) / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
