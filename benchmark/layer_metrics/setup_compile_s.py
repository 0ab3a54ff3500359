"""Seconds of lowering + backend compile before the window, from
``jax.monitoring`` (a read from the persistent cache counts with its read
time): near 0 on every run of a cell after its first in a checkout."""
LAYER = "entry / process (utils/compile_cache.py)"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(record):
    return record.get("setup_compile_s")
