"""Host milliseconds a step that the interpreter's garbage collector held
every Python thread of the process: seconds of the program's ``host.gc``
spans (any thread; a collection of generation 2, or any of a millisecond or
more) that start inside the window, over the program's count of
``train.step`` spans. It should read 0 or near it: the loop runs one step
ahead of the device, so a short pause costs nothing, and a full collection
in the steady loop is rare — which is what a stall of one run in a dozen
looks like. The window's collections by generation go out as a note line."""
import json

from benchmark.harness import cells

_hostgc = cells.load_module("layer_metrics", "_hostgc")

LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_samples_per_s"


def read(record):
    found = _hostgc.window(record)
    if found is None:
        return None
    inside = found["in_window"]
    print(json.dumps({"window_gc": {
        "steps": len(found["steps"]), "spans": len(inside),
        "by_generation": _hostgc.by_generation(inside)}}), flush=True)
    return 1e3 * sum(s["t1"] - s["t0"] for s in inside) / len(found["steps"])
