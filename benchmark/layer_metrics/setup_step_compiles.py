"""``compile.backend`` spans before the window that descend from a
``train.step`` span (a compile inside ``step_fn``'s call of the jitted
step): 1 says the step was compiled, or read from the cache, once; 2 says
its first two calls had different jit cache keys — a second trace, lowering
and cache read of the largest program of the start."""
from benchmark.harness import cells

_startup = cells.load_module("layer_metrics", "_startup")

LAYER = _startup.LAYER
UNIT = "count"
SOURCE = "program_span"
MOVES = "setup_s"


def read(record):
    startup = _startup.spans(record)
    if startup is None:
        return None
    by_id = {s["span_id"]: s for s in startup["all"]}
    return sum(1 for s in startup["before"] if s["name"] == _startup.BACKEND
               and _startup.descends_from(s, "train.step", by_id))
