"""Milliseconds a step the trainer's thread is blocked waiting for the
loader's next batch (the program's ``io.next_batch.wait`` spans on the
thread that runs ``train.step``; the reader thread's and the workers' own
waits are not the trainer waiting)."""
from benchmark.harness import program_trace

LAYER = "input pipeline (io/dataloader.py, spmd.shard_batch)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(record):
    return program_trace.span_ms_per_step(record, ("io.next_batch.wait",))
