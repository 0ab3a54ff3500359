"""The held experts' gemms against their roofline: the least time the chip
could take for them — the larger of their FLOPs over the bf16 peak and
their bytes over the HBM peak (``harness/peaks.py``) — over the device time
under the expert layer's ``moe.experts`` scope (the grouped matmuls and the
SwiGLU between them; forward, recomputed forward where the trace shows one,
backward). Rows are the pairs that land here on average: tokens x experts
per token x held / all; the row buffer's padding (``held_rows_factor``)
and a masked tile lower the share. At 256 rows an expert the gemms are
WEIGHT-bound (171 FLOPs a weight byte against the chip's 240): the byte
bound is the larger."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

REMAT = "rematted_computation"


def expert_layers(sizes):
    """Expert blocks a step runs: the layers after the dense ones, and one
    in every MTP module."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    return (sizes["num_hidden_layers"] - dense
            + sizes.get("num_nextn_predict_layers", 0))


def held_rows(sizes, tokens):
    return (tokens * sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"])


def held_gemm_flops(sizes, tokens, passes=3):
    """FLOPs of one train step's held-expert gemms: three matrices (gate,
    up, down) of hidden x width, 2 a multiply-add, on the rows that land
    here, in every expert block; ``passes`` gemm-sized passes (forward, the
    two backward gemms of each: 3; 4 with a recomputed forward)."""
    return (float(passes) * expert_layers(sizes) * 3 * 2
            * sizes["hidden_size"] * sizes["moe_intermediate_size"]
            * held_rows(sizes, tokens))


def held_gemm_bytes(sizes, tokens, passes=3, itemsize=2):
    """Least bytes the same gemms move: every held expert's three matrices
    once a pass (read forward and for the input gradient, written as weight
    gradient), and each row's input and output of each gemm."""
    h, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    weights = 3 * sizes["n_routed_experts"] * h * w
    acts = held_rows(sizes, tokens) * (2 * (h + w) + (w + h))
    return float(passes) * expert_layers(sizes) * itemsize * (weights + acts)


def read(record):
    sizes = record.get("sizes", {})
    if "router_experts" not in sizes:
        return None

    def in_experts(name, op):
        return "moe.experts" in _op_names.scopes(op)

    ms = _op_names.union_ms_per_step(record, in_experts)
    if not ms:
        return None
    recomputed = any(REMAT in op.split("/") for name, op, _, _ in
                     _op_names.op_events(record) if in_experts(name, op))
    passes = 4 if recomputed else 3
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = record["rows_per_step"] // record["chips"] * seq
    peaks = record["peaks"]
    least_s = max(
        held_gemm_flops(sizes, tokens, passes) / peaks["bf16_flops_per_s"],
        held_gemm_bytes(sizes, tokens, passes) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
