"""The part of the collective time per step during which no other
operation ran on that device: what the all-reduce adds to the step."""
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
    return coll["exposed_s"] / record["trace_steps"] * 1e3
