"""Seconds before the window in ``compile.lower`` + ``compile.backend``
spans of programs whose ``cache`` attribute is ``uncached``: jax compiled
them and wrote nothing (under ``configure_compile_cache()``'s threshold of
1 s), so they are compiled again at EVERY start, warm or cold — the
leaf-by-leaf parameter draw's one program a shape, ``convert_element_type``,
the key splits. What a lower threshold, or fewer programs, would move."""
import json

from benchmark.harness import cells

_startup = cells.load_module("layer_metrics", "_startup")

LAYER = _startup.LAYER
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(record):
    startup = _startup.spans(record)
    if startup is None:
        return None
    lower_s = backend_s = 0.0
    programs = 0
    for backend, lower in _startup.compiles(startup["before"]):
        if backend["attrs"].get("cache") == "uncached":
            programs += 1
            backend_s += backend["t1"] - backend["t0"]
            if lower is not None:
                lower_s += lower["t1"] - lower["t0"]
    print(json.dumps({"startup_uncached": {
        "programs": programs, "lower_s": round(lower_s, 3),
        "backend_s": round(backend_s, 3)}}), flush=True)
    return lower_s + backend_s
