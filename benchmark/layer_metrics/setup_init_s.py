"""Main-thread seconds before the window inside the program's ``nn.init``
spans (one a parameter drawn, ``nn.Layer.create_parameter``) and
``train.init_state`` spans (``init_fn``: parameters copied and placed, the
optimizer's state made and placed), LESS the trace / lower / backend spans
inside them — what drawing and placing cost beyond the programs compiled on
the way, which ``setup_trace_s`` / ``setup_compile_s`` hold. The split by
span name (count, seconds with the compiles in, bytes) goes out as a note
line, with ``train.build_step`` and ``io.loader.start`` beside it."""
import json

from benchmark.harness import cells

_startup = cells.load_module("layer_metrics", "_startup")

LAYER = _startup.LAYER
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"

NOTED = _startup.INIT + ("train.init_state.params",
                         "train.init_state.opt_state", "train.build_step",
                         "io.loader.start")


def read(record):
    startup = _startup.spans(record)
    if startup is None:
        return None
    split = {}
    for s in startup["main"]:
        if s["name"] in NOTED:
            row = split.setdefault(s["name"], {"n": 0, "s": 0.0, "bytes": 0})
            row["n"] += 1
            row["s"] = round(row["s"] + s["t1"] - s["t0"], 4)
            row["bytes"] += s["attrs"].get("bytes", 0)
    print(json.dumps({"startup_spans": split}), flush=True)
    return _startup.account(startup)["init"]
