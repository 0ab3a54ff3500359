"""Device milliseconds a step under the step program's ``clip`` and
``optimizer`` scopes (gradient clipping and the parameter update of
``spmd.build_train_step``; traced slice, one device)."""
from benchmark.harness import program_trace

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.union_ms_per_step(
        record, lambda scope: scope["phase"] in ("clip", "optimizer"))
