"""The gated short convolution's stage against its roofline: the least time
the chip could take to move the bytes of the passes THAT RAN — at the HBM
peak of ``harness/peaks.py`` — over the device time of the events under
``shortconv.stage``. The stage is element-wise (two gates and
``conv_L_cache`` taps a channel: about 10 FLOPs a channel-token against 8
bytes), so the byte bound is its roofline.

The bytes are the stage's least, from tokens, channels and the streams'
item size (``stage_bytes``): forward B, C and u read and ``C * conv(B u)``
written; backward B, C, u and the cotangent read and three cotangents
written. The function says nothing of what implements the stage — XLA
operations under a ``jax.checkpoint`` today, float32 arrays and all — so
the share reads the same work under a kernel later. What the backward
rebuilds inside the stage is the implementation's choice and counts as no
work, which lowers the share, as it should.

The passes are counted from the trace, as ``kda_core_roofline`` counts its
scan's: a forward where operations under the scope ran outside the backward
pass, one more where they ran inside a BLOCK's ``rematted_computation``
(per-block recomputation: the scope lies under it), a backward where they
ran under ``transpose(`` outside it (the stage's own rebuilt float32 lies
under the scope and is part of that backward)."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "short convolution (ops/linear_attention.py, text/models.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPE = "shortconv.stage"
REMAT = "rematted_computation"


def stage_bytes(tokens, channels, forwards=1, backwards=0, itemsize=2):
    """Least bytes of ``forwards`` forward and ``backwards`` backward passes
    of the stage over ``tokens`` tokens of ``channels`` channels: forward
    three streams read and one written, backward four read (the cotangent
    too) and three written; the taps (a few kB) count for nothing."""
    return float(tokens) * channels * itemsize * (4 * forwards
                                                  + 7 * backwards)


def in_stage(name, op):
    return SCOPE in _op_names.scopes(op)


def passes(record):
    """(forward, backward) passes a step, from the operations under the
    scope: what ran, not what a configuration says."""
    seen = set()
    for name, op, _, _ in _op_names.op_events(record):
        if not in_stage(name, op):
            continue
        outside = op.split("/")[:_op_names.scopes(op).index(SCOPE)]
        if "transpose(" not in op:
            seen.add("forward")
        elif REMAT in outside:
            seen.add("recomputed")
        else:
            seen.add("backward")
    return len(seen & {"forward", "recomputed"}), len(seen & {"backward"})


def conv_layers(sizes):
    """Short-convolution layers the step runs."""
    types = [sizes["layer_types"][i] for i in sizes["run_layers"]]
    return types.count("conv")


def read(record):
    sizes = record.get("sizes", {})
    if "conv_L_cache" not in sizes:
        return None
    ms = _op_names.union_ms_per_step(record, in_stage)
    if not ms:
        return None
    seq = record["traffic"]["fields"][0]["shape"][0]
    tokens = (record["rows_per_step"] // record["chips"] * seq
              * conv_layers(sizes))
    forwards, backwards = passes(record)
    least_s = (stage_bytes(tokens, sizes["hidden_size"], forwards, backwards)
               / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
