"""The streaming flash kernel's share of the chip's bf16 peak at
Granite-4.0-H's attention layer (``num_attention_heads`` = 32 QUERY heads of
``head_dim`` = 64, causal, nothing rotated, the scores at 1/64; one layer in
ten, one row of 8,192): the FLOPs of the full-causal calls THAT RAN
(``flash_stream_*`` by their own names) over their device time over the
peak of ``harness/peaks.py``, counted as ``gqa_flash_roofline`` counts: its
``calls`` and ``core_flops`` are used as they are, through
``swa_flash_roofline.share_of_peak``. The FLOPs are the model's: every
query head's causal pairs, the backward's one recomputed QK^T; that K and V
reach the kernel repeated 4 x to the query heads is the program's choice
and counts as no work. It is ``attn64_flash_roofline``'s geometry (LFM2's)
at a quarter of its rows with nothing before the core."""
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
    if "attention_multiplier" not in sizes:
        return None
    return _swa.share_of_peak(
        record, KERNEL, FORWARD, BACKWARD,
        lambda rows, seq, forwards, backwards: core_flops(
            rows, sizes["num_attention_heads"], seq, sizes["head_dim"],
            forwards, backwards))
