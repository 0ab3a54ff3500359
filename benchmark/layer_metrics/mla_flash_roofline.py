"""The streaming flash kernel's share of the chip's bf16 peak at latent
attention's two widths (keys ``qk_nope_head_dim + qk_rope_head_dim`` = 192,
values ``v_head_dim`` = 128): the FLOPs of the calls THAT RAN over the
device time of the kernel's Mosaic calls (``flash_stream_fwd``,
``flash_stream_bwd_dq``, ``flash_stream_bwd_dkv``, found by name in the
traced slice) over the peak of ``harness/peaks.py``. The work that runs is
counted from the trace: every ``flash_stream_fwd`` event is one forward
(under per-block recomputation there are two a block a step), every
``flash_stream_bwd_dkv`` event one backward. Compute-bound (8,192 keys:
over 2,000 FLOPs a byte of q, k, v). The FLOPs are the algorithm's: the
backward's one recomputed QK^T counts, the second one (the kernel's two
backward calls each recompute the scores) does not, which lowers the share,
as it should."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = ("attention dispatch, kernels (ops/attention.py, "
         "ops/pallas/flash_attention.py)")
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

KERNEL, FORWARD, BACKWARD = ("flash_stream_", "flash_stream_fwd",
                             "flash_stream_bwd_dkv")


def core_flops(batch, heads, seq, d_qk, d_v, forwards=1, backwards=1,
               causal=True):
    """FLOPs of ``forwards`` forward and ``backwards`` backward calls of one
    attention core: forward QK^T (d_qk) and PV (d_v); backward the scores
    again, dQ and dK (d_qk each), dP and dV (d_v each); 2 a multiply-add,
    over the seq (seq + 1) / 2 causal (query, key) pairs."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    forward = d_qk + d_v
    backward = 3 * d_qk + 2 * d_v
    return 2.0 * batch * heads * pairs * (forwards * forward
                                          + backwards * backward)


def core_bytes(batch, heads, seq, d_qk, d_v, forwards=1, backwards=1,
               itemsize=2):
    """Least bytes: forward q, k (d_qk) and v read, o (d_v) written;
    backward q, k, v, o, dO read and dq, dk, dv written."""
    forward = 2 * d_qk + 2 * d_v
    backward = 4 * d_qk + 4 * d_v
    return float(batch * heads * seq * itemsize
                 * (forwards * forward + backwards * backward))


def read(record):
    sizes = record.get("sizes", {})
    if "qk_rope_head_dim" not in sizes:
        return None

    def of_kind(kind):
        return lambda name, op: kind in name or kind in op

    ms = _op_names.union_ms_per_step(record, of_kind(KERNEL))
    if not ms:
        return None
    calls = {kind: sum(of_kind(kind)(name, op) for name, op, _, _ in
                       _op_names.op_events(record)) / record["trace_steps"]
             for kind in (FORWARD, BACKWARD)}
    seq = record["traffic"]["fields"][0]["shape"][0]
    flops = core_flops(
        record["rows_per_step"] // record["chips"],
        sizes["num_attention_heads"], seq,
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        sizes["v_head_dim"], calls[FORWARD], calls[BACKWARD])
    return 100.0 * flops / (ms / 1e3) / record["peaks"]["bf16_flops_per_s"]
