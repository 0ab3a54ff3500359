"""The streaming flash kernel's share of the chip's bf16 peak at gated
grouped-query attention's shape (``num_attention_heads`` QUERY heads of
``head_dim`` = 256 wide keys and values, causal; the layers that are full
attention, one in ``full_attention_interval``): the FLOPs of the calls THAT
RAN over the device time of the kernel's Mosaic calls (``flash_stream_*``,
found by name in the traced slice) over the peak of ``harness/peaks.py``.
The calls are counted from the trace: an event whose OWN name holds
``flash_stream_fwd`` is one forward (one a block where ``recompute`` keeps
the kernel's residuals, two where it does not), one whose name holds
``flash_stream_bwd_dkv`` one backward (the one-pass call
``flash_stream_bwd_dkv_dq`` or the two-call backward's second). An XLA
relayout that inherits a kernel's ``op_name`` is not a call; where no
event's own name holds a kernel's (a trace that names its events
otherwise) the ``op_name`` decides, as in ``mla_flash_roofline``. The FLOPs
are the model's: every query head's causal pairs, the backward's one
recomputed QK^T; that K and V reach the kernel repeated to the query heads
is the program's choice and counts as no work."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

KERNEL, FORWARD, BACKWARD = ("flash_stream_", "flash_stream_fwd",
                             "flash_stream_bwd_dkv")


def core_flops(batch, heads, seq, d, forwards=1, backwards=1, causal=True):
    """FLOPs of ``forwards`` forward and ``backwards`` backward calls of one
    attention core of ``heads`` query heads, keys and values ``d`` wide:
    forward QK^T and PV; backward the scores again, dQ, dK, dP and dV; 2 a
    multiply-add, over the seq (seq + 1) / 2 causal (query, key) pairs."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    return 2.0 * batch * heads * pairs * d * (2 * forwards + 5 * backwards)


def core_bytes(batch, heads, kv_heads, seq, d, forwards=1, backwards=1,
               itemsize=2):
    """Least bytes: forward q read and o written a query head, k and v read
    a key/value head; backward q, o, dO read and dq written a query head,
    k, v read and dk, dv written a key/value head."""
    forward = 2 * heads + 2 * kv_heads
    backward = 4 * heads + 4 * kv_heads
    return float(batch * seq * d * itemsize
                 * (forwards * forward + backwards * backward))


def calls(record, kind):
    """Calls of the kernel ``kind`` in the traced slice: events whose own
    name holds it; the ``op_name`` where no event's name does."""
    events = _op_names.op_events(record)
    named = sum(kind in name for name, _, _, _ in events)
    return named or sum(kind in op for _, op, _, _ in events)


def read(record):
    sizes = record.get("sizes", {})
    if "partial_rotary_factor" not in sizes:
        return None
    ms = _op_names.union_ms_per_step(
        record, lambda name, op: KERNEL in name or KERNEL in op)
    if not ms:
        return None
    seq = record["traffic"]["fields"][0]["shape"][0]
    flops = core_flops(
        record["rows_per_step"] // record["chips"],
        sizes["num_attention_heads"], seq, sizes["head_dim"],
        calls(record, FORWARD) / record["trace_steps"],
        calls(record, BACKWARD) / record["trace_steps"])
    return 100.0 * flops / (ms / 1e3) / record["peaks"]["bf16_flops_per_s"]
