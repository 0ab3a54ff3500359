"""Communication-efficiency meta-optimizers: DGC, LocalSGD, FP16AllReduce.

Reference: python/paddle/distributed/fleet/meta_optimizers/
dgc_optimizer.py + paddle/fluid/operators/dgc_op.cc (top-k gradient
sparsification with momentum correction + error feedback),
localsgd_optimizer.py (k local steps, periodic parameter average),
fp16_allreduce_optimizer.py (grads cast to fp16 for the allreduce).

TPU-native design: the SPMD train step normally lets XLA insert one
fused gradient psum over the data axes. These optimizers need the
PER-WORKER gradient before that reduction, so they compute fwd+bwd
inside ``jax.shard_map`` over the data axes:

- **fp16_allreduce**: local grads cast to fp16 -> psum over ICI (halves
  collective bytes — the one place compression genuinely maps to TPU)
  -> cast back.
- **DGC**: per-shard momentum correction (u = m*u + g), error
  accumulation (v += u), top-k selection by |v|; only selected entries
  enter the psum, exactly the dgc_op.cc algorithm. On ICI the dense
  masked psum moves the same bytes (XLA has no sparse allreduce), so
  what this preserves is DGC's *optimization dynamics* (error feedback
  ensures every coordinate is eventually applied) — models tuned with
  DGC converge identically.
- **LocalSGD** (``build_localsgd_train_step``): parameters and optimizer
  state carry a leading [D] axis sharded over the data axes — each
  worker owns a diverging replica — and every k-th step the replicas are
  pmean-averaged inside the same compiled step (lax.cond on the step
  counter, no host round-trip).
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from ..core import dispatch, random as random_core
from ..core.tensor import Tensor
from ..obs import tracing
from . import topology


def dgc_sparsify(g, u, v, momentum, sparsity):
    """One DGC step for a single gradient tensor (local, pre-allreduce).

    Returns (send, new_u, new_v): `send` is the dense tensor holding only
    the top-(1-sparsity) fraction of |v| (rest zero) to be summed across
    workers; u/v are cleared at the sent coordinates (error feedback).
    Reference: paddle/fluid/operators/dgc_op.cc.
    """
    u = momentum * u + g
    v = v + u
    flat = jnp.abs(v.reshape(-1))
    k = max(1, int(round(flat.size * (1.0 - sparsity))))
    kth = jax.lax.top_k(flat, k)[0][-1]
    mask = (jnp.abs(v) >= kth).astype(v.dtype)
    send = v * mask
    keep = 1.0 - mask
    return send, u * keep, v * keep


def make_local_grad_fn(forward_loss, data_axes, param_names,
                       fp16_allreduce=False, dgc_configs=None):
    """Wrap a forward_loss into a shard_map'd per-worker value-and-grad
    with the requested gradient-communication transform.

    forward_loss(params, buffers, x, y, key) -> (loss, new_buffers).
    Returns f(params, buffers, x, y, key, comm_state) ->
    (loss, grads, new_buffers, new_comm_state) operating on GLOBAL arrays
    (params/buffers replicated, x/y sharded over data_axes, comm_state
    sharded on its leading worker axis).
    """
    momentum = float((dgc_configs or {}).get("momentum", 0.9))
    sparsity = float((dgc_configs or {}).get("sparsity", [0.999])[-1]
                     if isinstance((dgc_configs or {}).get("sparsity"), list)
                     else (dgc_configs or {}).get("sparsity", 0.999))

    def local_fn(params, buffers, x, y, key, comm_state):
        # x/y arrive as this worker's shard; params/buffers replicated.
        # decorrelate dropout across workers (reference: each trainer
        # process seeds its own RNG)
        for ax in data_axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        (loss, new_buffers), grads = jax.value_and_grad(
            lambda p: forward_loss(p, buffers, x, y, key), has_aux=True)(params)
        new_comm = comm_state
        if dgc_configs is not None:
            new_comm = {}
            sends = {}
            for n in param_names:
                u, v = comm_state[n]
                send, nu, nv = dgc_sparsify(grads[n], u[0], v[0],
                                            momentum, sparsity)
                sends[n] = send
                new_comm[n] = (nu[None], nv[None])
            grads = sends
        if fp16_allreduce:
            grads = {n: g.astype(jnp.float16) for n, g in grads.items()}
        # pmean, not psum: the local grad is d(local mean loss)/dp, and
        # the global loss is the mean of the local means (DataParallel /
        # Reducer averaging semantics)
        for ax in data_axes:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, ax), grads)
            loss = jax.lax.pmean(loss, ax)
        if fp16_allreduce:
            grads = {n: grads[n].astype(jnp.float32) for n in param_names}
        # buffer updates are identical across workers (stats of the local
        # shard differ; average them like the reference's synced BN stats)
        for ax in data_axes:
            new_buffers = jax.tree.map(
                lambda b: jax.lax.pmean(b, ax)
                if jnp.issubdtype(jnp.result_type(b), jnp.floating) else b,
                new_buffers)
        return loss, grads, new_buffers, new_comm

    return local_fn


def init_dgc_state(params0, mesh, data_axes):
    """u/v accumulators with a leading worker axis sharded over the data
    axes (each worker's error-feedback state is its own)."""
    world = 1
    for ax in data_axes:
        world *= mesh.shape[ax]
    state = {}
    for n, p in params0.items():
        z = jnp.zeros((world,) + tuple(p.shape), jnp.float32)
        sharding = NamedSharding(mesh, P(data_axes))
        state[n] = (jax.device_put(z, sharding), jax.device_put(z, sharding))
    return state


@tracing.spanned("train.build_step", builder="localsgd")
def build_localsgd_train_step(layer, loss_fn, optimizer, mesh=None,
                              k_steps=4, amp_level="O0",
                              amp_dtype="bfloat16", adaptive=False,
                              init_k_steps=1, begin_step=1):
    """LocalSGD compiled train step (reference:
    fleet/meta_optimizers/localsgd_optimizer.py): every worker keeps its
    own parameter replica and optimizer state, runs local updates on its
    batch shard, and every ``k_steps`` the replicas are averaged with a
    pmean inside the same compiled step.

    ``adaptive=True`` is AdaptiveLocalSGD (reference:
    localsgd_optimizer.py:194 AdaptiveLocalSGDOptimizer): the sync
    interval k is recomputed at every sync from loss/LR progress,
    ``k = clip(ceil(sqrt(lr_0 * avg_loss / (lr * loss_0) * init_k)),
    1, 16)`` with loss_0/lr_0 captured at step 1 — the interval SHRINKS
    as the loss falls (replicas fine-tuning need tighter sync) and
    grows again as the LR decays. Until ``begin_step`` the
    replicas average every step, as in the reference. The whole
    adaptation (k, last-sync step, the loss_0/lr_0 snapshot) is carried
    as compiled scalars, so there is still no host round-trip.

    Returns (step_fn, init_fn); step_fn(params, opt_state, x, y, key, lr)
    -> (loss, params, opt_state) where params carry a leading [D] worker
    axis (use ``average_params`` to collapse for eval/save). With
    ``adaptive=True``, ``step_fn.comm_state['comm']['k']`` holds the
    current interval.
    """
    mesh = mesh or topology.get_global_mesh()
    data_axes = tuple(ax for ax in ("dp", "sharding")
                      if mesh.shape.get(ax, 1) > 1)
    if not data_axes:
        raise ValueError("LocalSGD needs a data-parallel mesh axis >1")
    world = int(np.prod([mesh.shape[ax] for ax in data_axes]))
    params0, buffers0 = layer.functional_state()
    param_names = list(params0)
    if any(getattr(p, "mp_spec", None) is not None
           for _, p in layer.named_parameters()):
        raise NotImplementedError(
            "LocalSGD composes with data parallelism only (reference "
            "localsgd_optimizer.py has the same constraint)")
    amp_enabled = amp_level in ("O1", "O2")

    def forward_loss(params, x, y, key):
        saved_p = {n: p._value for n, p in layer.named_parameters()}
        saved_b = dict(buffers0)
        try:
            with contextlib.ExitStack() as stack:
                stack.enter_context(dispatch.trace_mode())
                # traced inside the shard_map below: already per device
                stack.enter_context(topology.tracing_for(mesh))
                stack.enter_context(random_core.rng_guard(key))
                if amp_enabled:
                    from ..amp.auto_cast import auto_cast as _auto_cast
                    stack.enter_context(_auto_cast(
                        enable=True, level=amp_level, dtype=amp_dtype))
                from ..nn.aux_loss import (clear_direct_aux_losses,
                                           collect_aux_losses,
                                           sweep_direct_aux_losses,
                                           total_aux_loss)

                layer.load_functional_state(params, buffers0)
                with collect_aux_losses() as auxes:
                    clear_direct_aux_losses(layer)
                    out = layer.forward(Tensor(x, stop_gradient=True))
                    sweep_direct_aux_losses(layer, auxes)
                out_arr = out._value if isinstance(out, Tensor) else out
                return loss_fn(out_arr, y) + total_aux_loss(auxes)
        finally:
            layer.load_functional_state(saved_p, saved_b)

    hypers = optimizer._hypers()
    l1_coeff = type(optimizer)._take_l1(hypers)
    opt_update = type(optimizer)._update
    grad_clip = optimizer._grad_clip

    def local_step(params, opt_state, comm, x, y, key, lr, step_i):
        # everything here is per-worker: params/opt_state leading axis 1
        params = {n: params[n][0] for n in param_names}
        loss, grads = jax.value_and_grad(
            lambda p: forward_loss(p, x, y, key))(params)
        if grad_clip is not None:
            names = list(grads)
            clipped = grad_clip.clip_arrays([grads[n] for n in names])
            grads = dict(zip(names, clipped))
        new_params, new_state = {}, {}
        for n in param_names:
            g = grads[n].astype(params[n].dtype)
            if l1_coeff:
                g = g + l1_coeff * jnp.sign(params[n])
            st = tuple(a[0] for a in opt_state[n])
            out = opt_update(params[n], g, lr, *st, **hypers)
            new_params[n] = out[0]
            new_state[n] = tuple(out[1:])
        # periodic average: lax.cond keeps the collective inside the
        # compiled step (reference inserts c_allreduce every k-th step)
        def avg(ps):
            for ax in data_axes:
                ps = jax.tree.map(lambda a: jax.lax.pmean(a, ax), ps)
            return ps

        avg_loss = loss
        for ax in data_axes:
            avg_loss = jax.lax.pmean(avg_loss, ax)
        new_comm = comm
        if adaptive:
            # AdaptiveLocalSGD (reference localsgd_optimizer.py:420):
            # next_k = clip(ceil(sqrt(lr_0*avg_loss/(lr*loss_0)*init_k)))
            step = step_i + 1  # 1-based like the reference counter
            loss0 = jnp.where(step == 1, avg_loss, comm["loss0"])
            lr0 = jnp.where(step == 1, lr, comm["lr0"])
            due = (step - comm["last"]) >= comm["k"]
            sync = jnp.where(step <= begin_step, True, due)
            next_k = jnp.clip(jnp.ceil(jnp.sqrt(
                lr0 * avg_loss * float(init_k_steps)
                / (lr * loss0 + 1e-12))), 1, 16).astype(jnp.int32)
            new_comm = {
                "k": jnp.where((step > begin_step) & due, next_k,
                               comm["k"]),
                "last": jnp.where(sync, step, comm["last"]),
                "loss0": loss0,
                "lr0": lr0,
            }
        else:
            sync = (step_i % k_steps) == (k_steps - 1)
        new_params = jax.lax.cond(sync, avg, lambda ps: ps, new_params)
        return (avg_loss, {n: new_params[n][None] for n in param_names},
                {n: tuple(a[None] for a in new_state[n])
                 for n in param_names}, new_comm)

    pspec = P(data_axes)
    repl = P()
    comm_spec = {"k": repl, "last": repl, "loss0": repl, "lr0": repl}
    smapped = shard_map(
        local_step, mesh=mesh,
        in_specs=({n: pspec for n in param_names},
                  {n: (pspec,) * len(optimizer._init_state(params0[n]))
                   for n in param_names},
                  comm_spec, pspec, pspec, repl, repl, repl),
        out_specs=(repl, {n: pspec for n in param_names},
                   {n: (pspec,) * len(optimizer._init_state(params0[n]))
                    for n in param_names}, comm_spec),
        check_vma=False)
    step_jit = jax.jit(smapped)
    counter = {"i": 0, "comm": None}

    def _init_comm():
        return {"k": jnp.asarray(init_k_steps, jnp.int32),
                "last": jnp.asarray(0, jnp.int32),
                "loss0": jnp.asarray(0.0, jnp.float32),
                "lr0": jnp.asarray(0.0, jnp.float32)}

    def step_fn(params, opt_state, x, y, key=None, lr=None):
        if key is None:
            key = jax.random.PRNGKey(counter["i"])
        if lr is None:
            lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        if counter["comm"] is None:
            counter["comm"] = _init_comm()
        i = jnp.asarray(counter["i"], jnp.int32)
        loss, params, opt_state, counter["comm"] = step_jit(
            params, opt_state, counter["comm"], x, y, key, lr, i)
        counter["i"] += 1
        return loss, params, opt_state

    step_fn.comm_state = counter

    def init_fn():
        from .spmd import _count_leaves

        # one loop places a parameter's replicas and its optimizer state
        # together: one span, no ``.params`` / ``.opt_state`` children
        with tracing.span("train.init_state") as sp:
            params = {}
            opt_state = {}
            for n in param_names:
                rep = jnp.broadcast_to(jnp.asarray(params0[n]),
                                       (world,) + tuple(params0[n].shape))
                params[n] = jax.device_put(rep, NamedSharding(mesh, pspec))
                st = optimizer._init_state(params0[n])
                opt_state[n] = tuple(
                    jax.device_put(
                        jnp.broadcast_to(a, (world,) + tuple(a.shape)),
                        NamedSharding(mesh, pspec)) for a in st)
            _count_leaves(sp, (params, opt_state))
        return params, opt_state

    return step_fn, init_fn


def average_params(params, layer=None):
    """Collapse LocalSGD's leading worker axis by averaging; optionally
    write the result back onto the layer for eval/save."""
    avg = {n: jnp.mean(v, axis=0) for n, v in params.items()}
    if layer is not None:
        layer.load_functional_state(avg, None)
    return avg
