"""Collective time per train step on one device: the union of the
collective operations' intervals in the traced slice (XLA's all-reduce of
the dp gradients), over the slice's steps."""
from benchmark.harness import xplane

LAYER = "collectives (XLA all-reduce of the dp step)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    trace = record.get("trace")
    coll = xplane.collectives(trace) if trace else None
    if coll is None:
        return None
    return coll["total_s"] / record["trace_steps"] * 1e3
