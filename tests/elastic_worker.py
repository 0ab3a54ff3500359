"""Elastic pod trainer driven by tests/test_elastic.py.

Two layouts over the same elastic protocol:

- default (gloo): every rank joins a jax.distributed gang with ONE
  virtual CPU device (the layout that kills the gloo TCP framing race,
  see dist_llama_worker.py) and trains one GLOBAL dp=world mesh with
  ZeRO-1 (``sharding_stage=1``) so the optimizer state is genuinely
  sharded ACROSS PROCESSES — the multi-process checkpoint staging then
  writes real per-rank shards, and resume onto a different world size
  exercises reshard-on-load.
- ``--local``: no cross-process collectives — each rank trains an
  identical replica (same seed, same global batch). This is the layout
  for host-LOSS chaos (SIGKILL): survivors are never wedged in a
  collective, so the dead-host consensus can actually save.

argv: ckpt_root report_dir total_steps [--local]
env:  PADDLE_TPU_CHAOS           fault spec (chaos.arm_from_env)
      PADDLE_TPU_ELASTIC_RESAVE  optional second root: after a resumed
                                 load, immediately re-save the loaded
                                 state there (the bit-identity oracle)
      PADDLE_TPU_ELASTIC_*       protocol knobs (see resilience.elastic)

Per-rank exit contract (asserted by the e2e): a consensus save writes
report_dir/rank-<r>.json with the saved step and exits 143 on EVERY
rank; a completed run writes final_step/losses/stragglers and exits 0.
"""
import json
import os
import signal
import sys
import time

# one virtual CPU device per rank, BEFORE any jax backend touch
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import nn, optimizer  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed import checkpoint as dckpt  # noqa: E402
from paddle_tpu.distributed import spmd, topology  # noqa: E402
from paddle_tpu.obs import goodput  # noqa: E402
from paddle_tpu.resilience import chaos, elastic, preemption  # noqa: E402

GLOBAL_BATCH = 16


def _write_report(report_dir, rank, payload):
    os.makedirs(report_dir, exist_ok=True)
    from paddle_tpu.resilience.checkpoint import atomic_write_json

    atomic_write_json(os.path.join(report_dir, f"rank-{rank}.json"), payload)


def _goodput_exposition():
    from paddle_tpu.obs import prometheus

    return [line for line in prometheus.render().splitlines()
            if line.startswith("paddle_goodput_seconds_total")]


def main():
    argv = [a for a in sys.argv[1:] if a != "--local"]
    # --local also spellable as env (the launch_mod CLI can't pass
    # flag-looking script args through argparse)
    local = ("--local" in sys.argv[1:]
             or os.environ.get("PADDLE_TPU_ELASTIC_LOCAL") == "1")
    ckpt_root, report_dir, total_steps = argv[0], argv[1], int(argv[2])
    resave_root = os.environ.get("PADDLE_TPU_ELASTIC_RESAVE")

    chaos.arm_from_env()
    rank = int(os.environ.get("PADDLE_TRAINER_ID") or 0)
    world = int(os.environ.get("PADDLE_TRAINERS_NUM") or 1)

    if local:
        mesh = topology.build_mesh(dp=1)
        topology.set_global_mesh(mesh)
    else:
        dist.init_parallel_env()
        mesh = topology.get_global_mesh()

    import jax.numpy as jnp

    paddle.seed(7)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    opt = optimizer.Adam(1e-2, parameters=model.parameters())

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    step_fn, init_fn = spmd.build_train_step(
        model, loss_fn, opt, mesh=mesh,
        sharding_stage=0 if local else 1)
    params, st = init_fn()

    handler = preemption.get_preemption_handler()
    handler.install(signals=(signal.SIGTERM,))
    # collective (gloo) training must NOT park at a boundary waiting
    # for consensus (peers inside the next step's collective would
    # wedge): block only in the collective-free --local layout
    el = elastic.init_from_env(handler=handler, block=local)
    mgr = dckpt.sharded_checkpoint_manager(
        ckpt_root, rank=rank, world=world, barrier=el.barrier)
    mgr.reader_like = {"params": params, "opt_state": st,
                       "step": np.int64(0)}

    start = 0
    if mgr.latest_step() is not None:
        state, got = mgr.load()
        available = got if got >= 0 else None
        resume_at, _info = preemption.resolve_resume_step(
            ckpt_root, available_step=available, world_size=world)
        if resume_at is not None and state is not None:
            params, st = state["params"], state["opt_state"]
            start = int(resume_at)
        preemption.clear_resume_marker(ckpt_root)
        if resave_root and start > 0:
            # bit-identity oracle: republish the loaded state (possibly
            # on a DIFFERENT slice shape than the writer's) untouched
            remgr = dckpt.sharded_checkpoint_manager(
                resave_root, rank=rank, world=world, barrier=el.barrier)
            remgr.save({"params": params, "opt_state": st,
                        "step": np.int64(start)}, start)

    def batch(i):
        rng = np.random.RandomState(1000 + i)
        x = rng.rand(GLOBAL_BATCH, 8).astype(np.float32)
        y = rng.rand(GLOBAL_BATCH, 4).astype(np.float32)
        if local:
            return x, y
        shard = GLOBAL_BATCH // world
        return (x[rank * shard:(rank + 1) * shard],
                y[rank * shard:(rank + 1) * shard])

    # tests/test_elastic.py pads each step so a kill, a dead-host
    # timeout or a launcher signal lands while steps are underway
    step_sleep = float(os.environ.get("PADDLE_TPU_ELASTIC_STEP_SLEEP", 0.0))

    losses = []
    step = start

    def consensus_save_exit(target, params, st):
        state = {"params": params, "opt_state": st,
                 "step": np.int64(target)}
        mgr.save(state, target)
        if rank == 0:
            preemption.write_resume_marker(ckpt_root, step=target,
                                           world_size=world)
        el.saved(target)
        _write_report(report_dir, rank,
                      {"preempted": True, "step": target, "rank": rank})
        el.close()
        raise preemption.PreemptedExit(step=target)

    try:
        while step < total_steps:
            # t0 covers the chaos site too: injected delays (the
            # straggler probe) must land INSIDE the gossiped duration
            t0 = time.perf_counter()
            chaos.hit("train.step")
            x, y = batch(step)
            xg = spmd.shard_batch(x, mesh)
            yg = spmd.shard_batch(y, mesh)
            loss, params, st = step_fn(params, st, xg, yg)
            losses.append(float(jax.device_get(loss)))  # true sync
            if step_sleep:
                time.sleep(step_sleep)
            dt = time.perf_counter() - t0
            step += 1
            el.note_step(step, dt)
            target = el.check_boundary(step)
            if target is not None and step >= target:
                consensus_save_exit(target, params, st)
        # completion drain: stay responsive until every alive rank is
        # done — a straggler must not lose its coordinator because the
        # fast ranks finished, and a consensus triggered during the
        # drain (a host dies under the straggler) still saves. A
        # consensus step beyond our horizon clamps to the final step
        # (every rank shares total_steps, so the clamp is collective-
        # consistent).
        target = el.finish_and_drain(step)
        if target is not None:
            consensus_save_exit(min(target, step), params, st)
    except elastic.ElasticError as e:
        # coordinator lost / consensus timed out: a solo save would be
        # torn — exit preempted WITHOUT saving, resume from last good
        _write_report(report_dir, rank,
                      {"aborted": str(e), "rank": rank})
        el.close()
        sys.exit(preemption.EXIT_CODE)

    payload = {"completed": True, "final_step": step, "rank": rank,
               "losses": losses}
    if rank == 0:
        status = el.status()
        payload["stragglers"] = status.get("stragglers", [])
        payload["goodput"] = goodput.report()
        payload["prometheus_goodput"] = _goodput_exposition()
    _write_report(report_dir, rank, payload)
    el.close()


if __name__ == "__main__":
    main()
