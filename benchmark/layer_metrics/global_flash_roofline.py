"""The streaming flash kernel's share of the chip's bf16 peak at Trinity's
full-attention layers (``num_attention_heads`` = 32 QUERY heads of
``head_dim`` = 128 at every earlier key, no positions; one layer in
``global_attn_every_n_layers``): the FLOPs of the full-causal calls THAT RAN
(``flash_stream_*`` by name — the sliding layers' banded calls are
``flash_band_*`` and are ``swa_flash_roofline``'s) over their device time
over the peak of ``harness/peaks.py``, counted as ``gqa_flash_roofline``
counts: its ``calls`` and ``core_flops`` are used as they are."""
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
    if "global_attn_every_n_layers" not in sizes:
        return None
    return _swa.share_of_peak(
        record, KERNEL, FORWARD, BACKWARD,
        lambda rows, seq, forwards, backwards: core_flops(
            rows, sizes["num_attention_heads"], seq, sizes["head_dim"],
            forwards, backwards))
