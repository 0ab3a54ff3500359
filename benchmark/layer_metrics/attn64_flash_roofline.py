"""The streaming flash kernel's share of the chip's bf16 peak at LFM2's
attention layer (``num_attention_heads`` = 32 QUERY heads of ``head_dim`` =
64, causal; one layer in four of the period, one of the five run): the
FLOPs of the full-causal calls THAT RAN (``flash_stream_*`` by their own
names) over their device time over the peak of ``harness/peaks.py``,
counted as ``gqa_flash_roofline`` counts: its ``calls`` and ``core_flops``
are used as they are (``flash_roofline`` would multiply by
``num_hidden_layers``, and one layer in five has a core). The FLOPs are the
model's: every query head's causal pairs, the backward's one recomputed
QK^T; that K and V reach the kernel repeated 4 x to the query heads is the
program's choice and counts as no work. A 64-wide head fills half of the
MXU's 128-deep contraction and half of a 128-lane group in VMEM, which is
what the share shows."""
from benchmark.harness import cells

_gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
_swa = cells.load_module("layer_metrics", "swa_flash_roofline")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

core_flops, core_bytes = _gqa.core_flops, _gqa.core_bytes
KERNEL, FORWARD, BACKWARD = _gqa.KERNEL, _gqa.FORWARD, _gqa.BACKWARD


def read(record):
    sizes = record.get("sizes", {})
    if "conv_L_cache" not in sizes:
        return None
    return _swa.share_of_peak(
        record, KERNEL, FORWARD, BACKWARD,
        lambda rows, seq, forwards, backwards: core_flops(
            rows, sizes["num_attention_heads"], seq, sizes["head_dim"],
            forwards, backwards))
