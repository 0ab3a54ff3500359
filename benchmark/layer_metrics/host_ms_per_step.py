"""Host milliseconds a step that the trainer's thread spends in the
program's own train-path spans (``paddle_tpu.obs.tracing``, read inside the
program): ``io.next_batch`` + ``spmd.shard_batch`` + ``train.step`` in the
window, over the program's count of ``train.step`` spans. The loop runs one
step ahead of the device, so this costs nothing until it nears the step
time. The split (each span's total, ``train.step``'s self time) goes out as
a note line."""
import json

from benchmark.harness import program_trace

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_samples_per_s"

NAMES = ("io.next_batch", "spmd.shard_batch", "train.step")


def read(record):
    value = program_trace.span_ms_per_step(record, NAMES)
    if value is not None:
        print(json.dumps({"program_spans": program_trace.span_split(record)}),
              flush=True)
    return value
