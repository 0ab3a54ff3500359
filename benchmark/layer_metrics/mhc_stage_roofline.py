"""The residual path's STREAM side against its roofline: the least time the
chip could take to move the bytes of the passes THAT RAN — at the HBM peak
of ``harness/peaks.py`` — over the device time of the events under
``mhc.pre`` (``u = H_pre X``) and ``mhc.post`` (``X <- H_res X + H_post^T
y``). Both are element-wise (about 2 n + 2 n^2 FLOPs a stream feature
against 4 n + 2 bytes of float32): the byte bound is the roofline.

The bytes are a sublayer's least, from tokens, the hidden size, the number
of streams and the item sizes (``stage_bytes``): forward, the streams read
once and written once, u written and y read — the write-back of one
sublayer and the read of the next can be one pass over the streams —;
backward, the streams, y and the two cotangents (of the new streams and of
u) read, the streams' and y's cotangents written. The function says nothing
of what implements the stage — XLA operations under a ``jax.checkpoint`` a
stage today, which read the streams twice forward and rebuild float32
products in the backward — so the share reads the same work under a kernel
later. The streams, u and their cotangents count at the residual stream's
item size (float32 under amp O1, as in every LM configuration), y and its
cotangent at the matmuls' (bf16).

The time is what lies UNDER the two scopes: where XLA fuses the read's sum
into the RMSNorm that follows it (it does, forward: 0.03 ms a step under
``mhc.pre``, PR 53), that pass counts under the norm's module scope — a
fusion counts at its root — and the share over-reads by it (75% read, about
66% with the two forwards added).

The passes are counted from the trace, as ``shortconv_stage_roofline``
counts its stage's: a forward where operations under the scopes ran outside
the backward pass, one more where they ran inside a BLOCK's
``rematted_computation``, a backward where they ran under ``transpose(``
outside it."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_mhc = cells.load_module("layer_metrics", "mhc_ms_per_step")

LAYER = _mhc.LAYER
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPES = {"mhc.pre", "mhc.post"}
REMAT = "rematted_computation"


def stage_bytes(tokens, hidden, n, forwards=1, backwards=0, itemsize=4,
                y_itemsize=2):
    """Least bytes of ``forwards`` forward and ``backwards`` backward passes
    of one sublayer's read and write-back over ``tokens`` tokens of ``n``
    streams of ``hidden`` features: forward n + n + 1 stream-sized units
    (X read, X' written, u written) and y; backward 3 n + 1 (X, dX' read,
    dX written, du read) and y, dy. The maps' 24 numbers a token count for
    nothing."""
    forward = itemsize * (2 * n + 1) + y_itemsize
    backward = itemsize * (3 * n + 1) + 2 * y_itemsize
    return float(tokens) * hidden * (forward * forwards
                                     + backward * backwards)


def passes(record):
    """(forward, backward) passes a step, from the operations under the
    scopes: what ran, not what a configuration says."""
    keep, seen = _mhc.under(SCOPES), set()
    for name, op, _, _ in _op_names.op_events(record):
        if not keep(name, op):
            continue
        parts = _op_names.scopes(op)
        at = min(parts.index(s) for s in SCOPES if s in parts)
        if "transpose(" not in op:
            seen.add("forward")
        elif REMAT in op.split("/")[:at]:
            seen.add("recomputed")
        else:
            seen.add("backward")
    return len(seen & {"forward", "recomputed"}), len(seen & {"backward"})


def sublayers(sizes):
    """Hyper-connected sublayers a step runs: two a block, the MTP modules'
    blocks among them."""
    return 2 * (sizes["num_hidden_layers"]
                + sizes.get("num_nextn_predict_layers", 0))


def read(record):
    sizes = record.get("sizes", {})
    if "hc_mult" not in sizes:
        return None
    ms = _op_names.union_ms_per_step(record, _mhc.under(SCOPES))
    if not ms:
        return None
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = (record["rows_per_step"] // record["chips"] * seq
              * sublayers(sizes))
    forwards, backwards = passes(record)
    least_s = (stage_bytes(tokens, sizes["hidden_size"], sizes["hc_mult"],
                           forwards, backwards)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
