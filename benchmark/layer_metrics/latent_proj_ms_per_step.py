"""Device milliseconds a step under LatentMoE's two shared projections, the
expert layer's ``latentmoe.down`` (hidden -> latent, before the dispatch)
and ``latentmoe.up`` (latent -> hidden, after the combine) scopes; forward,
recomputed forward and backward, every expert layer. ``moe_ms_per_step``
leaves them out: they are ``Linear`` modules inside the layer. Traced
slice, one device; None for a program without such a layer."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "expert layer (incubate/moe.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

SCOPES = {"latentmoe.down", "latentmoe.up"}


def read(record):
    return _op_names.union_ms_per_step(
        record, lambda name, op: SCOPES & set(_op_names.scopes(op)))
