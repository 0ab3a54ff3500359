"""The BANDED streaming kernel's share of the chip's bf16 peak (a sliding
window of ``sliding_window`` keys, ``num_attention_heads`` QUERY heads of
``head_dim``): the model's FLOPs of the calls THAT RAN over the device time
of the banded Mosaic calls (``flash_band_*``, found by their own names in
the traced slice) over the peak of ``harness/peaks.py``. The FLOPs are the
BAND's: ``sum_i min(i + 1, window)`` (query, key) pairs a head, forward
QK^T and PV, backward the scores again, dQ, dK, dP and dV. What a kernel
computes beside the band (the masked corners of its edge tiles, a whole
causal triangle if it did not shrink its grid) is no work of the model's
and counts for nothing, so the share cannot pass 100 however the kernel is
implemented. Calls are counted as ``gqa_flash_roofline`` counts them: an
event whose OWN name holds ``flash_band_fwd`` is one forward (one a block
where ``recompute`` keeps the kernel's residuals), one whose name holds
``flash_band_bwd_dkv`` one backward (the one-pass call or the two-call
backward's second)."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

KERNEL, FORWARD, BACKWARD = ("flash_band_", "flash_band_fwd",
                             "flash_band_bwd_dkv")


def band_pairs(seq, window):
    """(query, key) pairs of one head under a causal window: query i sees
    ``min(i + 1, window)`` keys."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def band_flops(batch, heads, seq, window, d, forwards=1, backwards=1):
    """FLOPs of ``forwards`` forward and ``backwards`` backward calls of one
    windowed attention core of ``heads`` query heads, keys and values ``d``
    wide: 2 a multiply-add, 2 products forward and 5 backward, over the
    band's pairs."""
    return (2.0 * batch * heads * band_pairs(seq, window) * d
            * (2 * forwards + 5 * backwards))


def band_bytes(batch, heads, kv_heads, seq, d, forwards=1, backwards=1,
               itemsize=2):
    """Least bytes: the full core's (``gqa_flash_roofline.core_bytes``) — a
    window saves pairs, every row of q, k, v and o still moves once."""
    return _gqa.core_bytes(batch, heads, kv_heads, seq, d, forwards,
                           backwards, itemsize)


def share_of_peak(record, kernel, forward, backward, flops):
    """100 x ``flops(rows a chip, seq, forward calls a step, backward calls
    a step)`` over the device time of the events under ``kernel``'s name
    over the bf16 peak; None where the trace holds no such event."""
    ms = _op_names.union_ms_per_step(
        record, lambda name, op: kernel in name or kernel in op)
    if not ms:
        return None
    steps = record["trace_steps"]
    work = flops(record["rows_per_step"] // record["chips"],
                 record["traffic"]["fields"][0]["shape"][0],
                 _gqa.calls(record, forward) / steps,
                 _gqa.calls(record, backward) / steps)
    return 100.0 * work / (ms / 1e3) / record["peaks"]["bf16_flops_per_s"]


def read(record):
    sizes = record.get("sizes", {})
    if "sliding_window" not in sizes:
        return None
    return share_of_peak(
        record, KERNEL, FORWARD, BACKWARD,
        lambda rows, seq, forwards, backwards: band_flops(
            rows, sizes["num_attention_heads"], seq, sizes["sliding_window"],
            sizes["head_dim"], forwards, backwards))
