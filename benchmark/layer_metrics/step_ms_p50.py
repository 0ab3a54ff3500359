"""Median time of one train step: the distance between the moments two
consecutive steps were seen done (``block_until_ready``) on the host clock,
over the whole window."""
import numpy as np

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    done = record.get("step_done")
    if not done or len(done) < 2:
        return None
    return float(np.median(np.diff(np.asarray(done)))) * 1e3
