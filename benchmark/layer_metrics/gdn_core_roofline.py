"""The gated delta rule's scan under ONE decay a head (Gated DeltaNet)
against its roofline: the least time the chip could take for the passes
THAT RAN — the larger of their FLOPs over the bf16 peak and their bytes over
the HBM peak (``harness/peaks.py``) — over the device time under
``gdn.core`` (``gdn_core_ms_per_step``). It reads the scope, so it holds
whether the scan is XLA operations or a Mosaic call.

The passes are counted from the trace as ``kda_core_roofline`` counts them:
a forward where operations under the scope ran outside the backward pass,
one more where they ran inside a block's ``rematted_computation``, a
backward where they ran under ``transpose(`` outside it.

The work is the MODEL's, from tokens, value heads, d_k, d_v and the chunk C
(``gdn_core_flops``: the products of ``kda_core_flops`` — the pair terms
are the same [C, d] x [d, C] products whether the decay factors out of them
or not). The least bytes are fewer than Kimi Delta Attention's: the decay is
ONE float32 a token and value head, not d_k of them, and q and k are read
once a KEY head (``key_heads`` of the ``heads`` value heads: 16 of 32), not
once a value head — what the program's repeat of q and k moves beyond that
lowers the share, as it should. At d 128, C 64 and two value heads a key
head: 142.6 kFLOP a token and head against 1.0 kB: 138 FLOPs a byte, under
the chip's 240, so the byte bound is the larger."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_core = cells.load_module("layer_metrics", "gdn_core_ms_per_step")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

REMAT = "rematted_computation"


#: the scan's products are the per-channel scan's — the pair terms are the
#: same [C, d] x [d, C] products whether the decay factors out of them or
#: not — so its count is ``kda_core_roofline``'s: a token and head, forward,
#: pair terms 2 x d_k (C + 1), the inverse 2/3 C^2, T K and T V
#: (C + 1)(d_k + d_v), W S, the state's update and q S 3 x 2 d_k d_v, A_qk U
#: (C + 1) d_v; a backward is twice a forward
gdn_core_flops = cells.load_module("layer_metrics",
                                   "kda_core_roofline").kda_core_flops


def gdn_core_bytes(tokens, heads, key_heads, d_k, d_v, forwards=1,
                   backwards=0, itemsize=2):
    """Least bytes a pass over ``tokens`` tokens: forward q and k (d_k) read
    at ``itemsize`` a KEY head, v (d_v) a value head, the decay and beta
    (one float32 each a value head) read and o (d_v) written in float32;
    backward those read again with dO, and dq, dk (a key head), dv, dg,
    dbeta written."""
    forward = (key_heads * itemsize * 2 * d_k
               + heads * (itemsize * d_v + 4 * (2 + d_v)))
    backward = 2 * forward
    return float(tokens) * (forwards * forward + backwards * backward)


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
    ms = _core.read(record)
    if "linear_num_value_heads" not in sizes or not ms:
        return None
    every = sizes["full_attention_interval"]
    layers = sizes["num_hidden_layers"] - sizes["num_hidden_layers"] // every
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = record["rows_per_step"] // record["chips"] * seq * layers
    forwards, backwards = passes(record)
    heads, key_heads = (sizes["linear_num_value_heads"],
                        sizes["linear_num_key_heads"])
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    peaks = record["peaks"]
    least_s = max(
        gdn_core_flops(tokens, heads, d_k, d_v, sizes.get("gdn_chunk", 64),
                       forwards, backwards) / peaks["bf16_flops_per_s"],
        gdn_core_bytes(tokens, heads, key_heads, d_k, d_v, forwards,
                       backwards) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
