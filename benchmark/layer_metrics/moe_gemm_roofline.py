"""The expert gemms' share of the chip's bf16 peak: their FLOPs from shapes
over the device time under the expert layer's ``moe.experts`` scope (the
grouped matmuls and the SwiGLU between them, forward and backward) over the
peak of ``harness/peaks.py``. Compute-bound: at 2,048 rows an expert the
three gemms do 683 FLOPs a weight byte. Only assigned rows count (tokens x
experts per token), so padding or a wasted tile lowers the share."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def expert_gemm_flops(sizes, tokens):
    """FLOPs of one train step's expert gemms: three matrices (gate, up,
    down) of hidden x width, 2 a multiply-add, on ``tokens`` x experts per
    token assigned rows, in every expert layer; x 3 for forward + the two
    backward gemms of each."""
    rows = tokens * sizes["num_experts_per_tok"]
    return (3.0 * sizes["num_hidden_layers"] * 3 * 2 * sizes["hidden_size"]
            * sizes["intermediate_size"] * rows)


def expert_gemm_bytes(sizes, tokens, itemsize=2):
    """Least bytes the same gemms move: every expert's three matrices once
    a pass (read forward and for the input gradient, written as weight
    gradient), and each assigned row's input and output of each gemm."""
    rows = tokens * sizes["num_experts_per_tok"]
    h, w = sizes["hidden_size"], sizes["intermediate_size"]
    weights = 3 * sizes["num_experts"] * h * w
    acts = rows * (2 * (h + w) + (w + h))
    return 3.0 * sizes["num_hidden_layers"] * itemsize * (weights + acts)


def read(record):
    if "num_experts_per_tok" not in record.get("sizes", {}):
        return None
    ms = _op_names.union_ms_per_step(
        record, lambda name, op: "moe.experts" in _op_names.scopes(op))
    if not ms:
        return None
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = record["rows_per_step"] // record["chips"] * seq
    flops = expert_gemm_flops(record["sizes"], tokens)
    return 100.0 * flops / (ms / 1e3) / record["peaks"]["bf16_flops_per_s"]
