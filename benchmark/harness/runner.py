"""What ``run.py`` and the tools share: the device checks that admit no
fallback, and the context a driver gets."""
import json
import os
import sys
import time

from . import cells, peaks
from .spans import SpanRecorder


class Context:
    """What a driver gets: the cell and its files, the seed, the window's
    length, the chips, and the harness's instruments."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def log(self, obj):
        print(json.dumps(obj, default=str), flush=True)

    def mark(self, name):
        """A point of the set-up's timeline, in seconds since the context
        was made (a note for PERF.md's "what set-up costs", not a metric)."""
        self.marks.append([name, round(time.monotonic() - self.t_made, 3)])


def fail(message, code=2):
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    sys.exit(code)


def make_context(bench, cell, seed, seconds, trace):
    """Check the machine, configure the compile cache by the repo's one
    rule, and collect the cell's files. Exits non-zero, having printed no
    result, without a TPU, on a device outside the peaks table, with too
    few chips, or where the program is not in the checkout."""
    root = cells.ROOT
    if not os.path.isdir(os.path.join(root, "paddle_tpu")):
        fail("the program (paddle_tpu/) is not in this checkout")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: jax reports platform {devices[0].platform!r}; the "
             "benchmark never falls back to another backend")
    try:
        peak = peaks.peaks_for(devices[0].device_kind)
    except KeyError as e:
        fail(str(e.args[0]))
    if len(devices) < cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, jax sees "
             f"{len(devices)}")
    devices = devices[:cell["chips"]]

    from paddle_tpu.utils.compile_cache import configure_compile_cache

    from .compile_meter import CompileMeter

    # the program's own rule and nothing beside it (it keeps compiles under
    # 1 s out of the cache): ``setup_s`` is a start-up a deployment sees
    cache_dir = configure_compile_cache()

    traffic = cells.load_json("traffic", cell["traffic"])
    out_dir = os.path.join(root, ".benchmark_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(
        bench=bench, cell=cell, traffic=traffic,
        sizes=cells.config_sizes(bench, cell["config"]),
        config=cells.load_module("configs", cell["config"]),
        reference=cells.load_module("references", cell["config"]),
        seed=seed, seconds=seconds, trace=bool(trace), devices=devices,
        peaks=peak, meter=CompileMeter(), recorder=SpanRecorder(),
        out_dir=out_dir, root=root, marks=[], t_made=time.monotonic())
    ctx.log({"workload": cell["name"], "seed": seed, "seconds": seconds,
             "trace": int(trace), "compile_cache": cache_dir,
             "device_kind": devices[0].device_kind, "chips": len(devices)})
    return ctx


def stop_children():
    """Every process this run started has ended before the result is out."""
    import multiprocessing

    for child in multiprocessing.active_children():
        child.terminate()
    for child in multiprocessing.active_children():
        child.join(10)
