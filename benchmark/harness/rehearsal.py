"""The rehearsal that costs no chip time: a driver run end to end at toy
width on the CPU (``JAX_PLATFORMS=cpu``), from ``benchmark/tests``. It
proves paths, arguments and control flow; its numbers are labelled
``rehearsal`` and are never printed under a metric's name."""
import json
import os

import time

from . import cells, runner
from .compile_meter import CompileMeter
from .spans import SpanRecorder


class RehearsalContext(runner.Context):
    """The driver's context, with notes kept for the test to read."""

    def __init__(self, **kw):
        super().__init__(notes=[], marks=[], t_made=time.monotonic(), **kw)

    def log(self, obj):
        self.notes.append(obj)


def rehearse(config_name, traffic, toy_sizes, out_dir, seconds=1.0, seed=0,
             trace=False, chips=1):
    """Run the traffic's driver on ``config_name`` with its sizes replaced
    by ``toy_sizes``; returns (result, notes)."""
    import jax

    with open(os.path.join(cells.BENCH_DIR, "configs",
                           config_name + ".json")) as f:
        sizes = dict(json.load(f), **toy_sizes)
    ctx = RehearsalContext(
        bench=None, cell={"name": "rehearsal", "config": config_name,
                          "chips": chips},
        traffic=traffic, sizes=sizes,
        config=cells.load_module("configs", config_name),
        reference=cells.load_module("references", config_name),
        seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:chips],
        peaks={"bf16_flops_per_s": float("nan")}, meter=CompileMeter(),
        recorder=SpanRecorder(), out_dir=out_dir, root=cells.ROOT,
        rehearsal=True)
    driver = cells.load_module("drivers", traffic["driver"])
    result = driver.run(ctx)
    result["rehearsal"] = True
    return result, ctx.notes
