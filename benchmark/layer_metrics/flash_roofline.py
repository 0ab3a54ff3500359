"""The streaming flash kernel's share of the chip's bf16 peak: the model's
attention FLOPs from shapes over the device time of the kernel's Mosaic
calls (``flash_stream_fwd``, ``flash_stream_bwd_dq``, ``flash_stream_bwd_dkv``
of ops/pallas/flash_attention.py, found by name in the traced slice) over
the peak of ``harness/peaks.py``. Compute-bound at these shapes (4,096 keys
of 128: 2,048 FLOPs a byte of q, k, v). The FLOPs are the model's, not the
kernel's: 2 score-sized matmuls forward and 5 backward on the causal half;
the kernel's two backward calls recompute the scores (it runs 9), which
lowers the share, as it should."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

KERNEL = "flash_stream_"


def attention_flops(batch, heads, seq, head_dim, layers=1, causal=True):
    """Model FLOPs of one train step's attention cores: 2 matmuls forward
    (QK^T, PV) and 5 backward (dV, dP, dQ, dK and the recomputed QK^T is
    NOT counted: it is the kernel's choice), 2 a multiply-add, over the
    seq (seq + 1) / 2 causal (query, key) pairs."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    return 7.0 * 2 * batch * heads * pairs * head_dim * layers


def attention_bytes(batch, heads, seq, head_dim, layers=1, itemsize=2):
    """Least bytes: q, k, v read and o written forward; q, k, v, o, dO read
    and dq, dk, dv written backward."""
    return 12.0 * batch * heads * seq * head_dim * itemsize * layers


def read(record):
    sizes = record.get("sizes", {})
    ms = _op_names.union_ms_per_step(
        record, lambda name, op: KERNEL in name or KERNEL in op)
    if not ms or "num_attention_heads" not in sizes:
        return None
    seq = record["traffic"]["fields"][0]["shape"][0]
    heads = sizes["num_attention_heads"]
    flops = attention_flops(
        record["rows_per_step"] // record["chips"], heads, seq,
        sizes["hidden_size"] // heads, sizes["num_hidden_layers"])
    return 100.0 * flops / (ms / 1e3) / record["peaks"]["bf16_flops_per_s"]
