"""LatentMoE's held experts' gemms against their roofline: the least time
the chip could take for the passes THAT RAN — the larger of their FLOPs over
the bf16 peak and their bytes over the HBM peak (``harness/peaks.py``) —
over the device time under the expert layers' ``moe.experts`` scope (the two
grouped matmuls and the relu² between them). ``moe_held_gemm_roofline``
counts three ``hidden x moe_intermediate`` matrices and finds its expert
layers from ``first_k_dense_replace``; here an expert is TWO matrices of
``moe_latent_size x moe_intermediate_size`` (1,024 x 2,688) and the expert
layers are the ``moe`` entries of ``layer_types`` and the MTP module's.

Rows are the pairs that land here on average: tokens x experts per token x
held / all (1,408 at 4,096 tokens: 176 an expert); the row buffer's padding
(``held_rows_factor``) and a masked tile lower the share. The passes are
``ssd_core_roofline``'s count, on this scope: a forward, one more where the
layer's recomputation ran it again, a backward (two gemm-sized products a
gemm). At 176 rows an expert the gemms are WEIGHT-bound (176 FLOPs a weight
byte, 142 a byte with the rows' own, against the chip's 240): the byte bound
is the larger."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")
_ssd = cells.load_module("layer_metrics", "ssd_core_roofline")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "moe.experts"


def expert_layers(sizes):
    """Expert layers a step runs: the ``moe`` layers of the run pattern and
    each MTP module's ``E`` layers."""
    run = list(sizes["layer_types"][:sizes["num_hidden_layers"]])
    return run.count("moe") + sizes.get("num_nextn_predict_layers", 0) * (
        sizes.get("mtp_hybrid_override_pattern", "").count("E"))


def held_rows(sizes, tokens):
    return (tokens * sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"])


def latent_gemm_flops(sizes, tokens, forwards=1, backwards=0):
    """FLOPs of the held experts' gemms in ``forwards`` forward and
    ``backwards`` backward passes of every expert layer: two matrices of
    latent x width, 2 a multiply-add, on the rows that land here; a
    backward is twice a forward (every product has two gradients)."""
    return (float(forwards + 2 * backwards) * expert_layers(sizes) * 2 * 2
            * sizes["moe_latent_size"] * sizes["moe_intermediate_size"]
            * held_rows(sizes, tokens))


def latent_gemm_bytes(sizes, tokens, forwards=1, backwards=0, itemsize=2):
    """Least bytes the same gemms move, a gemm-sized pass (a forward is
    one, a backward two): every held expert's two matrices once (read
    forward and for the input gradient, written as weight gradient), and
    each row's input and output of each gemm."""
    latent, w = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    weights = 2 * sizes["n_routed_experts"] * latent * w
    acts = held_rows(sizes, tokens) * 2 * (latent + w)
    return (float(forwards + 2 * backwards) * expert_layers(sizes) * itemsize
            * (weights + acts))


def read(record):
    sizes = record.get("sizes", {})
    if "moe_latent_size" not in sizes or "router_experts" not in sizes:
        return None
    ms = _op_names.union_ms_per_step(
        record, lambda name, op: SCOPE in _op_names.scopes(op))
    if not ms:
        return None
    forwards, backwards = _ssd.passes(record, SCOPE)
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = record["rows_per_step"] // record["chips"] * seq
    peaks = record["peaks"]
    least_s = max(
        latent_gemm_flops(sizes, tokens, forwards, backwards)
        / peaks["bf16_flops_per_s"],
        latent_gemm_bytes(sizes, tokens, forwards, backwards)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
