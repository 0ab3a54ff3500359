"""Backend compiles inside the measured window (``jax.monitoring``; for a
serving cell also the engine's ``compiles`` delta). Validity, not speed:
more than 0 makes the run incorrect, because warm-up missed a shape."""
LAYER = "entry / process (utils/compile_cache.py)"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(record):
    return record.get("window_compiles")
