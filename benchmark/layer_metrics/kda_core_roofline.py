"""The gated delta rule's chunked scan against its roofline: the least time
the chip could take for the passes THAT RAN — the larger of their FLOPs over
the bf16 peak and their bytes over the HBM peak (``harness/peaks.py``) —
over the device time under ``kda.core`` (``kda_core_ms_per_step``). It reads
the scope, so it holds whether the scan is XLA operations or a Mosaic call.

The passes are counted from the trace, as ``mla_flash_roofline`` counts its
kernel's calls: a forward where operations under the scope ran outside the
backward pass, one more where they ran inside a block's
``rematted_computation`` (per-block recomputation), a backward where they
ran under ``transpose(`` outside it. What the backward rebuilds of a segment
(``jax.checkpoint`` inside the scan) is the implementation's choice and
counts as no work, which lowers the share, as it should.

The work is the algorithm's, from tokens, heads, d_k, d_v and the chunk C
(``kda_core_flops``): a chunk's pair terms on the i <= r triangle for k and
for q, the inverse by substitution, T's two products, the state's read
(W S), its update and the two output products. At d 128 and C 64 that is
142.6 kFLOP a token and head against 1.8 kB moved: 79 FLOPs a byte, under
the chip's 240, so the byte bound is the larger."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_core = cells.load_module("layer_metrics", "kda_core_ms_per_step")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

REMAT = "rematted_computation"


def kda_core_flops(tokens, heads, d_k, d_v, chunk, forwards=1, backwards=0):
    """FLOPs of ``forwards`` forward and ``backwards`` backward passes of
    the chunked scan over ``tokens`` tokens (2 a multiply-add; a backward
    is twice a forward: every product has two gradients). A token and head,
    forward: the pair terms for k and for q on the triangle 2 x d_k (C + 1),
    the inverse 2/3 C^2, T K and T V (C + 1)(d_k + d_v) on the triangle,
    W S, the state's update and q S 3 x 2 d_k d_v, A_qk U (C + 1) d_v."""
    per_token = (2 * d_k * (chunk + 1) + 2 * chunk * chunk / 3
                 + (chunk + 1) * (d_k + d_v) + 6 * d_k * d_v
                 + (chunk + 1) * d_v)
    return float(tokens) * heads * per_token * (forwards + 2 * backwards)


def kda_core_bytes(tokens, heads, d_k, d_v, forwards=1, backwards=0,
                   itemsize=2):
    """Least bytes: forward q, k (d_k) and v (d_v) read at ``itemsize``, the
    decay (d_k) and beta read and o (d_v) written in float32; backward
    those read again with dO, and dq, dk, dv, dg, dbeta written."""
    forward = itemsize * (2 * d_k + d_v) + 4 * (d_k + 1 + d_v)
    backward = 2 * forward + 4 * d_k
    return float(tokens) * heads * (forwards * forward
                                    + backwards * backward)


def passes(record):
    """(forward, backward) passes a step, from the operations under the
    scope: what ran, not what a configuration says."""
    seen = set()
    for _, op, _, _ in _op_names.op_events(record):
        parts = _op_names.scopes(op)
        if _core.SCOPE not in parts:
            continue
        outside = op.split("/")[:parts.index(_core.SCOPE)]
        if "transpose(" not in op:
            seen.add("forward")
        elif REMAT in outside:
            seen.add("recomputed")
        else:
            seen.add("backward")
    return len(seen & {"forward", "recomputed"}), len(seen & {"backward"})


def read(record):
    sizes = record.get("sizes", {})
    linear = sizes.get("linear_attn_config")
    ms = _core.read(record)
    if not linear or not ms:
        return None
    layers = sum(i in linear["kda_layers"]
                 for i in range(1, sizes["num_hidden_layers"] + 1))
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = record["rows_per_step"] // record["chips"] * seq * layers
    forwards, backwards = passes(record)
    heads, d = linear["num_heads"], linear["head_dim"]
    peaks = record["peaks"]
    least_s = max(
        kda_core_flops(tokens, heads, d, d, sizes.get("kda_chunk", 64),
                       forwards, backwards) / peaks["bf16_flops_per_s"],
        kda_core_bytes(tokens, heads, d, d, forwards, backwards)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
