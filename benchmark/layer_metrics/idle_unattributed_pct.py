"""Share of the device's idle seconds in the traced slice (the gaps
between operations on one device) that no ``paddle_tpu:*`` host span of the
program overlaps: idle time the program's own spans cannot explain. High
with a small idle share is harmless (the host waits in the caller's
``block_until_ready`` while the device works); high with a large idle share
says the program has host work without a span."""
from benchmark.harness import program_trace, xplane

LAYER = "device (libtpu / XLA)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_per_s"


def read(record):
    ops = program_trace.ops(record)
    trace = program_trace.trace_of(record)
    spans = program_trace.host_spans(trace) if trace else []
    if not ops or not spans:
        return None
    busy = xplane.merge((s, e) for _, s, e in ops)
    idle = xplane.subtract([[busy[0][0], busy[-1][1]]], busy)
    if not idle:
        return 0.0
    covered = xplane.merge((s, e) for _, s, e in spans)
    return 100.0 * (xplane.total(xplane.subtract(idle, covered))
                    / xplane.total(idle))
