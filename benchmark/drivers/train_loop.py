"""train_loop: a training job as a trainer runs it.

One process; the model, loss and optimizer come from the configuration's
``build_train``; batches come from ``io.DataLoader`` worker processes over
the seeded generator (``harness/datasets.py``) and are placed with
``spmd.shard_batch``; the step is ``spmd.build_train_step(..., donate=True)``
on ``topology.build_mesh(dp=<chips>)``.

Set-up (not measured): build, compile the step on the loader's first batch,
the reference check, ``probe_steps`` steps on that one batch (its loss has
to fall; they warm every shape the window uses). The window then runs for
``seconds``: the loop waits for step i-1 after dispatching step i, so the
device always has the next step queued and every step's end is seen on the
host clock. The window ends when the last dispatched step is done
(``block_until_ready``). With ``--trace 1`` a slice of ``trace_steps`` more
steps runs under the profiler after the window, so the window's host-clock
numbers are taken with the profiler off.
"""
import os
import time

import numpy as np

from benchmark.harness import memory, tracing
from benchmark.harness.datasets import SeededDataset, field_shapes

#: batches the seeded dataset can deliver; far more than any window needs
MAX_BATCHES = 1 << 14


def run(ctx):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import io
    from paddle_tpu.distributed import spmd, topology

    traffic, sizes = ctx.traffic, ctx.sizes
    shapes = field_shapes(traffic)
    chips = len(ctx.devices)
    rows = traffic["rows_per_chip"] * chips
    rec = ctx.recorder

    ctx.mark("driver_start")
    built = ctx.config.build_train(ctx.seed, sizes, shapes)
    ctx.mark("model_built")
    layer, opt = built["layer"], built["optimizer"]
    layer.train()
    dataset = SeededDataset(traffic, sizes, ctx.seed, rows * MAX_BATCHES)
    loader = io.DataLoader(dataset, batch_size=rows, shuffle=False,
                           **traffic["loader"])
    batches = iter(loader)  # the workers fork here and start drawing

    mesh = topology.build_mesh(dp=chips, devices=ctx.devices)
    step_fn, init_fn = spmd.build_train_step(
        layer, built["loss_fn"], opt, mesh=mesh,
        amp_level=built["amp_level"], donate=True)
    params, opt_state = init_fn()
    ctx.mark("state_on_device")
    key = jax.random.PRNGKey(ctx.seed)
    state = {"params": params, "opt_state": opt_state, "i": 0}

    def fetch():
        with rec.span("next_batch"):
            xb, yb = next(batches)
        with rec.span("shard_batch"):
            return (spmd.shard_batch(xb, mesh), spmd.shard_batch(yb, mesh))

    def dispatch(x, y):
        with rec.span("dispatch"):
            k = jax.random.fold_in(key, state["i"])
            loss, state["params"], state["opt_state"] = step_fn(
                state["params"], state["opt_state"], x, y, key=k)
        state["i"] += 1
        return loss

    def loop(until):
        """Steps until ``until(n_dispatched)``; returns the losses (device
        scalars) and the host time at which each step was seen done."""
        losses, done, prev = [], [], None
        while True:
            x, y = fetch()
            losses.append(dispatch(x, y))
            if prev is not None:
                with rec.span("readback"):
                    prev.block_until_ready()
                done.append(time.monotonic())
            prev = losses[-1]
            if until(len(losses)):
                break
        with rec.span("readback"):
            prev.block_until_ready()
        done.append(time.monotonic())
        return losses, done

    # ---- set-up: compile on the first batch, check, probe ------------
    probe_x, probe_y = fetch()
    ctx.mark("first_batch")
    probe = [float(dispatch(probe_x, probe_y))]
    ctx.mark("step_compiled")
    check = ctx.config.check_train(built, ctx.reference, sizes, shapes,
                                   probe_x)
    ctx.mark("reference_checked")
    probe += [float(dispatch(probe_x, probe_y))
              for _ in range(traffic["probe_steps"] - 1)]
    loss_fell = bool(np.isfinite(probe).all() and min(probe[1:]) < probe[0])
    ctx.log({"reference_check": check, "probe_losses": probe,
             "loss_fell": loss_fell})
    loop(lambda n: n >= 2)  # the loop's own path (lagged wait), warm
    ctx.mark("window_start")

    # ---- the window ---------------------------------------------------
    before = ctx.meter.snapshot()
    t_start = time.monotonic()
    losses, done = loop(
        lambda n: time.monotonic() - t_start >= ctx.seconds)
    t_end = done[-1]
    after = ctx.meter.snapshot()
    values = np.asarray([float(v) for v in losses])
    window_compiles = after["compiles"] - before["compiles"]
    slowest = sorted(((t1 - t0, name, t0 - t_start)
                      for name, t0, t1 in rec.spans if t0 >= t_start),
                     reverse=True)[:3]
    ctx.log({"setup_timeline_s": ctx.marks, "window_slowest_spans": [
        {"span": n, "seconds": round(d, 4), "at_s": round(at, 3)}
        for d, n, at in slowest]})

    # ---- the traced slice (a run of its own kind: --trace 1) ----------
    trace = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.out_dir, "trace")
        rec.annotate = True
        tracing.start(trace_dir)
        try:
            loop(lambda n: n >= traffic["trace_steps"])
        finally:
            trace = tracing.stop_and_load(trace_dir)
            rec.annotate = False

    # ---- memory: the runtime's counters and the step program's needs --
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    compiled = step_fn.jitted.lower(
        state["params"], state["opt_state"], layer.functional_state()[1],
        probe_x, probe_y, key, lr).compile()
    programs = [memory.program_memory("train_step", compiled)]
    stats = memory.runtime_stats(ctx.devices)
    ctx.log({"memory_stats": stats, "programs": programs})

    steps = len(losses)
    seconds = t_end - t_start
    failed = int((~np.isfinite(values)).sum())
    flops = ctx.config.flops_per_sample(sizes, shapes)
    return {
        "correct": bool(check["ok"] and loss_fell and failed == 0
                        and window_compiles == 0),
        "attempted": steps,
        "failed": failed,
        "t_window_start": t_start,
        "end_to_end": {"train_samples_per_s": steps * rows / seconds},
        "memory_peak_bytes": memory.peak_bytes(stats, programs),
        "record": {
            "window": {"start": t_start, "end": t_end, "seconds": seconds},
            "steps": steps, "rows_per_step": rows, "chips": chips,
            "step_done": done, "spans": rec.spans,
            "flops_per_sample": flops, "programs": programs,
            "setup_compile_s": before["compile_s"],
            "window_compiles": window_compiles,
            "trace": trace, "trace_steps": traffic["trace_steps"],
        },
    }
