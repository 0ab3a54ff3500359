"""Process/env + dygraph DataParallel (reference: python/paddle/distributed/
parallel.py:57 init_parallel_env, python/paddle/fluid/dygraph/parallel.py:380
DataParallel; C++ imperative/reducer.cc).
"""
import os

import jax
import numpy as np
import jax.numpy as jnp

from ..nn.layer import Layer
from . import topology


class ParallelEnv:
    """reference: dygraph/parallel.py ParallelEnv (PADDLE_* env)."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID", jax.process_index()))
        self._world_size = int(os.environ.get("PADDLE_TRAINERS_NUM",
                                              jax.process_count()))
        self._device_id = 0

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def local_rank(self):
        return self._rank

    @property
    def nranks(self):
        return self._world_size

    @property
    def dev_id(self):
        return self._device_id

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:6170")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "127.0.0.1:6170")
        return eps.split(",")


_distributed_initialized = False


def init_parallel_env():
    """reference: distributed/parallel.py:57. On TPU this is
    jax.distributed.initialize (multi-host) + building the global mesh —
    the NCCL-ring bootstrap (gen_comm_id_helper.cc TCP exchange) is
    replaced by the JAX coordination service.

    Ordering is load-bearing: the cluster shape is read from PADDLE_*
    env vars ONLY (never from jax.process_count(), which would
    initialize the XLA backend) so that jax.distributed.initialize runs
    before any backend-touching JAX call, as it requires.
    """
    global _distributed_initialized
    try:
        n = int(os.environ.get("PADDLE_TRAINERS_NUM") or 1)
    except ValueError:
        n = 1
    coordinator = os.environ.get("PADDLE_COORDINATOR")
    if (n > 1 and coordinator and not _distributed_initialized
            and not jax.distributed.is_initialized()):
        # the coordination service races worker startup: early workers
        # see connection-refused/timeouts until the coordinator binds.
        # Backoff+jitter instead of crashing the whole gang (knobs:
        # PADDLE_TPU_RETRY_* env, see resilience.retry).
        from ..resilience.retry import call_with_retry

        deadline = float(os.environ.get("PADDLE_TPU_DIST_INIT_DEADLINE",
                                        300.0))

        def _transient(e):
            # jax wraps grpc coordination failures in RuntimeError; only
            # connection-flavored ones are worth waiting out — config
            # errors ("already called", bad address) must surface fast
            if not isinstance(e, RuntimeError):
                return True
            msg = str(e)
            return any(s in msg for s in (
                "UNAVAILABLE", "DEADLINE_EXCEEDED", "connect",
                "Connect", "timed out", "Timed out", "unavailable"))

        call_with_retry(
            jax.distributed.initialize,
            coordinator_address=coordinator,
            num_processes=n,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID") or 0),
            retry_on=(OSError, ConnectionError, TimeoutError, RuntimeError),
            retry_if=_transient,
            # connection-refused races resolve in seconds (refused
            # connects fail fast, so 5 attempts span ~15s of backoff);
            # jax's own initialization_timeout already waits minutes for
            # slow peers, so more attempts would multiply that, and the
            # deadline caps the total either way
            max_attempts=5, base_delay=1.0,
            max_delay=10.0, deadline=deadline)
        _distributed_initialized = True
    mesh = topology.build_mesh(dp=len(jax.devices()))
    topology.set_global_mesh(mesh)
    return ParallelEnv()


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    return jax.process_count()


import functools


@functools.lru_cache(maxsize=8)
def _grad_mean_fn(mesh):
    """One jitted mean-over-processes per mesh: the jit wrapper owns the
    executable cache, so rebuilding it per call would recompile every
    step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(lambda a: jnp.mean(a, axis=0),
                   out_shardings=NamedSharding(mesh, P()))


class DataParallel(Layer):
    """reference: dygraph/parallel.py:380 + reducer.cc bucketed allreduce.

    TPU-native: in the compiled SPMD path there is nothing to reduce —
    the batch axis is sharded over 'dp', parameters are replicated, and
    XLA inserts the gradient psum during the traced backward, so
    scale_loss/apply_collective_grads are identities there (gradient
    bucketing, reducer.cc's raison d'être, is subsumed by XLA collective
    fusion). In EAGER multi-process runs (one device per process, like
    the reference's one-proc-per-GPU trainers) each process holds local
    gradients, and apply_collective_grads really averages them across
    processes after backward() — the Reducer.MarkGroupReady/
    FusedAllReduceSchedule analog, batched per call instead of bucketed.
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        if jax.process_count() == 1:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        if len(jax.local_devices()) != 1:
            raise NotImplementedError(
                "eager DataParallel assumes one device per process (the "
                "reference's one-proc-per-GPU trainer model); with "
                "multiple local chips use spmd.build_train_step, which "
                "shards over the whole mesh")
        mesh = topology.get_global_mesh()
        n = jax.process_count()
        stack_sh = NamedSharding(mesh, P("dp"))
        mean0 = _grad_mean_fn(mesh)  # cached: compiled once per mesh
        for _, p in self._layers.named_parameters():
            if getattr(p, "_grad", None) is None:
                continue
            local = np.asarray(p._grad)[None]
            garr = jax.make_array_from_process_local_data(
                stack_sh, local, (n,) + local.shape[1:])
            out = mean0(garr)  # compiled psum over the process mesh
            p._grad = jnp.asarray(out.addressable_shards[0].data)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    @property
    def parameters_attr(self):
        return self._layers.parameters()

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)


def _spawn_target(func, args, rank, nprocs, master, backend):
    # runs in a FRESH interpreter (spawn context): set the cluster env
    # before any jax backend touch, then rendezvous and call user code
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_COORDINATOR"] = master
    if backend == "cpu":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, backend=None,
          **options):
    """reference: distributed/spawn.py:317.

    nprocs <= 1: one process already drives all local TPU chips via the
    mesh, so this is a direct call. nprocs > 1: real multiprocessing
    spawn — one process per rank rendezvousing through jax.distributed
    (func should call init_parallel_env() first, like the reference).
    backend='cpu' forces a single virtual CPU device per rank (the
    2-trainer localhost test harness)."""
    if nprocs is None or nprocs <= 1:
        func(*args)
        return None
    import multiprocessing as mp

    from .launch_mod import find_free_port

    ctx = mp.get_context("spawn")
    master = f"127.0.0.1:{find_free_port()}"
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_target,
                        args=(func, args, rank, nprocs, master, backend),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if not join:
        return procs
    for p in procs:
        p.join()
    bad = [p.exitcode for p in procs if p.exitcode != 0]
    if bad:
        raise RuntimeError(f"spawned trainers failed with exit codes {bad}")
    return None
