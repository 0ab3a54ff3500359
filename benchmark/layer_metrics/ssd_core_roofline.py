"""The selective state-space scan against its roofline: the least time the
chip could take for the passes THAT RAN — the larger of their FLOPs over the
bf16 peak and their bytes over the HBM peak (``harness/peaks.py``) — over
the device time under ``mamba.core`` (``ssd_core_ms_per_step``). It reads
the scope and says nothing of what implements the scan: XLA operations
today, a Mosaic kernel later, the same work.

The passes are counted from the trace, as ``kda_core_roofline`` counts its
scan's (``passes``, by scope): a forward where operations under the scope
ran outside the backward pass, one more where they ran inside a block's
``rematted_computation`` (per-block recomputation), a backward where they
ran under ``transpose(`` outside it. What the backward rebuilds of a segment
(``jax.checkpoint`` inside the scan) is the implementation's choice and
counts as no work, which lowers the share, as it should.

The work is the algorithm's, from tokens, heads H of P, the state's N, the
groups G and the chunk C (``ssd_core_flops``): a chunk's pair product ``C
B^T`` on the i <= r triangle ONCE A GROUP, each head's masked [C, C] x [C,
P] product on the triangle, the state's update and its read. The bytes are
the least (``ssd_core_bytes``): x read and y written a head, dt one float32
a head, B and C ONCE A GROUP — never a copy a head. At 64 heads of 64 on a
state of 128, one group and C 256 that is 3.18 MFLOP a token against 17.2
kB: 186 FLOPs a byte, under the chip's 240, so the byte bound is the
larger."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_core = cells.load_module("layer_metrics", "ssd_core_ms_per_step")

LAYER = "linear attention (ops/linear_attention.py, text/models.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

REMAT = "rematted_computation"


def ssd_core_flops(tokens, heads, d_head, d_state, groups, chunk, forwards=1,
                   backwards=0):
    """FLOPs of ``forwards`` forward and ``backwards`` backward passes of
    the chunked scan over ``tokens`` tokens (2 a multiply-add; a backward
    is twice a forward: every product has two gradients). A token, forward:
    the pair product on the triangle d_state (C + 1) a GROUP; a head's
    masked product on the triangle d_head (C + 1), the state's update and
    its read 2 x 2 d_state d_head."""
    per_token = (groups * d_state * (chunk + 1)
                 + heads * (d_head * (chunk + 1) + 4 * d_state * d_head))
    return float(tokens) * per_token * (forwards + 2 * backwards)


def ssd_core_bytes(tokens, heads, d_head, d_state, groups, forwards=1,
                   backwards=0, itemsize=2):
    """Least bytes: forward x read and y written at ``itemsize`` and dt read
    in float32 a head, B and C read ONCE A GROUP; backward x, dt, B, C and
    dy read, dx, d dt, dB and dC written."""
    forward = heads * (2 * d_head * itemsize + 4) + groups * 2 * d_state * (
        itemsize)
    backward = heads * (3 * d_head * itemsize + 8) + groups * 4 * d_state * (
        itemsize)
    return float(tokens) * (forwards * forward + backwards * backward)


def passes(record, scope=_core.SCOPE):
    """(forward, backward) passes a step, from the operations under
    ``scope``: what ran, not what a configuration says."""
    seen = set()
    for _, op, _, _ in _op_names.op_events(record):
        parts = _op_names.scopes(op)
        if scope not in parts:
            continue
        outside = op.split("/")[:parts.index(scope)]
        if "transpose(" not in op:
            seen.add("forward")
        elif REMAT in outside:
            seen.add("recomputed")
        else:
            seen.add("backward")
    return len(seen & {"forward", "recomputed"}), len(seen & {"backward"})


def mamba_tokens(record):
    """Tokens a chip and step through state-space layers: rows x seq x the
    ``mamba`` layers run."""
    sizes = record["sizes"]
    layers = list(sizes["layer_types"][:sizes["num_hidden_layers"]]).count(
        "mamba")
    seq = record["traffic"]["fields"][0]["shape"][0]
    return record["rows_per_step"] // record["chips"] * seq * layers


def read(record):
    sizes = record.get("sizes", {})
    ms = _core.read(record)
    if "mamba_n_heads" not in sizes or not ms:
        return None
    forwards, backwards = passes(record)
    shape = (mamba_tokens(record), sizes["mamba_n_heads"],
             sizes["mamba_d_head"], sizes["mamba_d_state"],
             sizes["mamba_n_groups"])
    peaks = record["peaks"]
    least_s = max(
        ssd_core_flops(*shape, sizes["mamba_chunk"], forwards,
                       backwards) / peaks["bf16_flops_per_s"],
        ssd_core_bytes(*shape, forwards, backwards)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
