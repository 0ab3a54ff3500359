"""The longest interval between the starts of two consecutive ``train.step``
spans on the trainer's thread in the window, less the median interval, ms:
how far the worst step of the run stood out on the host's clock. The account
of the three longest intervals, of the median one and of the window's two
ends (where the window waits for the host, and a stall is charged in full)
by class — a collection, a recompile, the loader, the placement, the step's
children, or no span of the program at all — goes out as a note line, so a
stalled run says on one line where its excess went."""
import json

from benchmark.harness import cells

_hostgc = cells.load_module("layer_metrics", "_hostgc")

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(record):
    found = _hostgc.window(record)
    value = _hostgc.stall_ms(found) if found else None
    if value is not None:
        print(json.dumps({"window_stalls": _hostgc.stretches(record, found)}),
              flush=True)
    return value
