"""Idle share of the device in the traced steady slice: 1 - (union of the
intervals in which an operation ran) / (first operation's start to the
last one's end), averaged over the cell's chips."""
from benchmark.harness import xplane

LAYER = "device (libtpu / XLA)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    trace = record.get("trace")
    busy = xplane.busy(trace) if trace else None
    if not busy or busy["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
