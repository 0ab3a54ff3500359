"""Share of the device's busy time in the traced slice spent in operations
that carry a program scope in their HLO ``op_name`` (a module's
``jax.named_scope`` from ``nn.Layer.__call__``, or ``loss`` / ``clip`` /
``optimizer``). What is left is XLA's own: copies, slices and converts it
inserted. Near 0 it says the executable came from a compile cache written
before the program had scopes (jax keeps metadata out of the cache key).
The ten scopes with most time go out as a note line."""
import json

from benchmark.harness import program_trace, xplane

LAYER = "model code (text/models.py, vision/models/resnet.py, nn/)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    all_ops = program_trace.ops(record)
    if not all_ops or not program_trace.program_has_scopes():
        return None
    busy = xplane.total(xplane.merge((s, e) for _, s, e in all_ops))
    scoped = xplane.total(xplane.merge(
        (s, e) for scope, s, e in all_ops if scope["phase"] is not None))
    print(json.dumps({"scope_table_ms_per_step":
                      program_trace.scope_table(record)}), flush=True)
    return 100.0 * scoped / busy
