"""Device milliseconds a step in operations under a hyper-connection's
scopes (``mhc.maps``, ``mhc.sinkhorn``, ``mhc.pre``, ``mhc.post``,
``mhc.expand``, ``mhc.reduce``: ``text.models.HyperConnection``,
``ops/hyper_connections.py``): the residual path's own work around every
sublayer — the maps from the streams, the Sinkhorn rounds, the read
``H_pre X`` and the write-back ``H_res X + H_post^T y`` — forward,
recomputed forward and backward (traced slice, one device). What the
sublayers themselves cost is not in it. None for a model without the
streams."""
from benchmark.harness import cells

_op_names = cells.load_module("layer_metrics", "_op_names")

LAYER = "residual path (ops/hyper_connections.py, text/models.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"

PREFIX = "mhc."


def under(scopes):
    """keep(event name, op_name): the operation lies under one of
    ``scopes`` (None: under any ``mhc.*`` scope)."""
    def keep(name, op):
        parts = _op_names.scopes(op)
        if scopes is None:
            return any(p.startswith(PREFIX) for p in parts)
        return bool(scopes & set(parts))
    return keep


def read(record):
    return _op_names.union_ms_per_step(record, under(None))
