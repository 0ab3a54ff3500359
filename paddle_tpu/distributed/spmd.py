"""SPMD train-step builder — the TPU-native ParallelExecutor/meta-optimizer.

Reference analogs: the multi-device SSA graph + allreduce op-handles
(framework/details/), GraphExecutionOptimizer, sharding_optimizer.py's
3k-line program surgery, TensorParallelOptimizer — all collapsed into:
pick a Mesh, annotate shardings, jit, let XLA insert ICI collectives
(the scaling-book recipe).

``build_train_step`` returns one compiled function
  (params, opt_state, batch, key, lr) -> (loss, params, opt_state)
with:
- batch sharded over 'dp' (data parallel: grad psum from SPMD),
- params sharded per-tensor over 'mp' if the layer attached an ``mp_spec``
  (tensor parallel), replicated otherwise,
- optimizer states sharded over 'dp'/'sharding' (ZeRO-1) when
  ``shard_optimizer=True``,
- optional jax.checkpoint (recompute) around the loss fn.
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import dispatch, random as random_core
from ..core.tensor import Tensor
from ..obs import tracing
from . import topology

#: where offloaded optimizer state lives between steps
_HOST_MEMORY_KIND = "pinned_host"


def param_sharding_spec(layer, mesh):
    """Per-parameter PartitionSpec: mp_spec annotation if present, else
    replicated. Returns dict name -> NamedSharding."""
    specs = {}
    for name, p in layer.named_parameters():
        spec = getattr(p, "mp_spec", None)
        specs[name] = NamedSharding(mesh, spec if spec is not None else P())
    return specs


def _zero1_spec(arr, mesh, axes=("dp", "sharding"), start=0, prefix=()):
    """Shard the FIRST divisible (and not already-sharded) dim of an
    optimizer-state array over the dp/sharding axes (ZeRO-1; reference
    sharding_optimizer.py shards by param — per-dim sharding is the
    XLA-friendly equivalent). ``start``/``prefix`` let callers protect
    leading structural dims (the pipeline's [stage, layer] stacking)
    and respect an existing sharding (pp/mp axes)."""
    n = 1
    for ax in axes:
        n *= mesh.shape.get(ax, 1)
    base = P(*prefix) if prefix else P()
    if n == 1 or arr.ndim == 0:
        return NamedSharding(mesh, base)
    for dim in range(start, arr.ndim):
        if dim < len(prefix) and prefix[dim] is not None:
            continue  # already sharded (pp / mp)
        if arr.shape[dim] % n == 0:
            spec = list(prefix) + [None] * (arr.ndim - len(prefix))
            spec[dim] = axes if len(axes) > 1 else axes[0]
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, base)


def _count_leaves(sp, tree):
    """A start-up span's ``leaves`` and ``bytes``: the arrays it placed."""
    leaves = jax.tree_util.tree_leaves(tree)
    sp.attrs.update(leaves=len(leaves),
                    bytes=sum(int(a.nbytes) for a in leaves))


def _spanned_init(place_params, place_opt_state):
    """The ``init_fn`` of a builder whose state is placed in two phases:
    one ``train.init_state`` span a call, a child span a phase, each with
    the ``leaves`` and ``bytes`` it placed."""
    def init_fn():
        with tracing.span("train.init_state") as sp:
            with tracing.span("train.init_state.params") as sp_params:
                params = place_params()
                _count_leaves(sp_params, params)
            with tracing.span("train.init_state.opt_state") as sp_opt:
                opt_state = place_opt_state()
                _count_leaves(sp_opt, opt_state)
            _count_leaves(sp, (params, opt_state))
        return params, opt_state
    return init_fn


@tracing.spanned("train.build_step", builder="spmd")
def build_train_step(layer, loss_fn, optimizer, mesh=None, recompute=False,
                     shard_optimizer=False, sharding_stage=None, donate=True,
                     amp_level="O0", amp_dtype="bfloat16",
                     fp16_allreduce=False, dgc_configs=None, strategy=None,
                     offload=False, bad_step_guard=False):
    """Compile the full distributed training step for `layer`.

    loss_fn(model_out, label_array) -> scalar (pure jnp).
    Returns (step_fn, init_fn) where init_fn() -> (params, opt_state) as
    properly-sharded global arrays, and
    step_fn(params, opt_state, x, y, key, lr) -> (loss, params, opt_state).

    amp_level "O1"/"O2" traces the forward under ``paddle.amp.auto_cast``
    (white/black-listed op casting, reference amp_auto_cast.cc) with
    fp32 master weights; bf16 needs no loss scaling on TPU, and grads come
    out fp32 via the loss. The cast decision is trace-time, so the compiled
    step has bf16 matmuls on the MXU with no per-step Python cost.

    bad_step_guard=True detects a non-finite loss or gradient INSIDE the
    compiled step and keeps the previous params/opt_state/buffers (a
    branchless jnp.where select — no host round-trip, donation-safe);
    step_fn then returns (loss, params, opt_state, bad) with ``bad`` a
    scalar bool array. Pair with resilience.BadStepMonitor to roll back
    to the last good checkpoint after N consecutive bad steps.

    sharding_stage (ZeRO; reference sharding_optimizer.py:40,84,180 does
    this with 3k lines of program surgery — here it is sharding specs):
      1: optimizer states sharded over dp+sharding (= shard_optimizer=True)
      2: + gradients sharding-constrained to the same spec, so XLA emits
         reduce-scatter for the grad psum instead of all-reduce
      3: + parameters STORED sharded between steps (all-gathered at use
         inside the step); param memory scales 1/N at rest
    """
    mesh = mesh or topology.get_global_mesh()
    if strategy is not None:
        # fleet DistributedStrategy knobs -> functional options (the
        # meta-optimizer stack of fleet_base.py:1242 collapsed into one
        # entry point; knobs without an implementation raise, never no-op)
        if strategy.adaptive_localsgd or strategy.localsgd:
            unsupported = [k for k in ("recompute", "dgc", "fp16_allreduce",
                                       "sharding")
                           if getattr(strategy, k)]
            if recompute:
                unsupported.append("recompute=True")
            if fp16_allreduce:
                unsupported.append("fp16_allreduce=True")
            if dgc_configs is not None:
                unsupported.append("dgc_configs")
            if offload:
                unsupported.append("offload=True")
            if sharding_stage:
                unsupported.append(f"sharding_stage={sharding_stage}")
            if unsupported:
                raise NotImplementedError(
                    f"localsgd does not compose with {unsupported}; "
                    f"disable them or drop localsgd")
            from . import comm_opt

            acfg = dict(strategy.adaptive_localsgd_configs or {}) \
                if strategy.adaptive_localsgd else {}
            return comm_opt.build_localsgd_train_step(
                layer, loss_fn, optimizer, mesh=mesh,
                k_steps=int(strategy.localsgd_configs.get("k_steps", 1) or 1),
                amp_level="O1" if strategy.amp else amp_level,
                amp_dtype=amp_dtype,
                adaptive=bool(strategy.adaptive_localsgd),
                init_k_steps=int(acfg.get("init_k_steps", 1) or 1),
                begin_step=int(acfg.get("begin_step", 1) or 1))
        if strategy.amp and amp_level == "O0":
            amp_level = "O2" if strategy.amp_configs.get("use_pure_fp16") \
                else "O1"
        recompute = recompute or strategy.recompute
        fp16_allreduce = fp16_allreduce or strategy.fp16_allreduce
        if strategy.dgc and dgc_configs is None:
            dgc_configs = dict(strategy.dgc_configs)
        if strategy.sharding and sharding_stage is None:
            sharding_stage = int(
                strategy.sharding_configs.get("stage", 1) or 1)
        offload = offload or bool(strategy.sharding_configs.get("offload"))
    if sharding_stage is None:
        # group_sharded_parallel() tags the model with its ZeRO stage
        sharding_stage = getattr(layer, "_sharding_stage", None) or \
            (1 if shard_optimizer else 0)
    if sharding_stage not in (0, 1, 2, 3):
        raise ValueError(f"sharding_stage must be 0..3, got {sharding_stage}")
    shard_optimizer = sharding_stage >= 1
    params0, buffers0 = layer.functional_state()
    param_names = list(params0)
    buffer_names = list(buffers0)
    p_shardings = param_sharding_spec(layer, mesh)
    if amp_level not in ("O0", "O1", "O2"):
        raise ValueError(f"amp_level must be 'O0'|'O1'|'O2', got {amp_level!r}")
    amp_enabled = amp_level in ("O1", "O2")

    def forward_loss(params, buffers, x, y, key):
        saved_p = {n: p._value for n, p in layer.named_parameters()}
        saved_b = dict(buffers0)
        try:
            with contextlib.ExitStack() as stack:
                stack.enter_context(dispatch.trace_mode())
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

                layer.load_functional_state(params, buffers)
                # auxiliary losses emitted during the forward (MoE
                # load-balancing etc.) join the objective; routing them
                # through the collector keeps tracers off the Layer
                with collect_aux_losses() as auxes:
                    clear_direct_aux_losses(layer)
                    # forward is called directly (no hooks on the root),
                    # so the root's scope is entered here
                    with jax.named_scope(layer.scope_name()):
                        out = layer.forward(Tensor(x, stop_gradient=True))
                    sweep_direct_aux_losses(layer, auxes)
                out_arr = out._value if isinstance(out, Tensor) else out
                with jax.named_scope("loss"):
                    loss = loss_fn(out_arr, y) + total_aux_loss(auxes)
                # capture in-forward buffer updates (BatchNorm running
                # stats, QAT moving scales) so they thread through the
                # compiled step instead of silently freezing at init
                _, new_buffers = layer.functional_state()
                return loss, {n: new_buffers.get(n, buffers[n])
                              for n in buffer_names}
        finally:
            layer.load_functional_state(saved_p, saved_b)

    if recompute:
        forward_loss = jax.checkpoint(forward_loss, static_argnums=())

    # a parameter's own hypers, as the eager step takes them (its
    # regularizer; AdamW's apply_decay_param_fun by its name)
    named = dict(layer.named_parameters())
    hypers = {n: optimizer._hypers(named[n]) for n in param_names}
    l1_coeff = {n: type(optimizer)._take_l1(h) for n, h in hypers.items()}
    opt_update = type(optimizer)._update
    grad_clip = optimizer._grad_clip

    # shardings: batch over dp(+sharding) — ZeRO groups subdivide dp
    repl = NamedSharding(mesh, P())
    zero_specs = {n: _zero1_spec(params0[n], mesh) for n in param_names}
    # per-state-array shardings (used by host offload to bounce each
    # state leaf host<->device; reference: sharding/offload_helper.py)
    opt_state_specs = {}
    if offload:
        if dgc_configs is not None:
            raise NotImplementedError("offload does not compose with dgc")
        for n in param_names:
            st = optimizer._init_state(params0[n])
            opt_state_specs[n] = tuple(
                (_zero1_spec(a, mesh) if sharding_stage >= 1 else repl)
                for a in st)
    has_mp = {n: getattr(named[n], "mp_spec", None) is not None
              for n in param_names}
    if sharding_stage >= 3:
        # params at REST live sharded (ZeRO-3); mp-annotated params keep
        # their tensor-parallel layout
        param_shards = {n: (p_shardings[n] if has_mp[n] else zero_specs[n])
                        for n in param_names}
    else:
        param_shards = {n: p_shardings[n] for n in param_names}
    data_axes = tuple(ax for ax in ("dp", "sharding") if mesh.shape.get(ax, 1) > 1)
    batch_shard = NamedSharding(mesh, P(data_axes)) if data_axes else repl

    use_local_grads = fp16_allreduce or dgc_configs is not None
    if use_local_grads:
        if any(has_mp.values()):
            raise NotImplementedError(
                "dgc/fp16_allreduce compose with data parallelism only "
                "(reference dgc_optimizer.py has the same constraint)")
        if sharding_stage >= 2:
            raise NotImplementedError(
                "dgc/fp16_allreduce replace the gradient allreduce and "
                "cannot combine with ZeRO-2/3 reduce-scatter")
        if not data_axes:
            raise ValueError(
                "dgc/fp16_allreduce need a data-parallel mesh axis > 1")
        from . import comm_opt

        local_grad_fn = comm_opt.make_local_grad_fn(
            forward_loss, data_axes, param_names,
            fp16_allreduce=fp16_allreduce, dgc_configs=dgc_configs)
        pspec = P(data_axes)
        local_grads_smapped = jax.shard_map(
            local_grad_fn, mesh=mesh,
            in_specs=({n: P() for n in param_names},
                      {n: P() for n in buffer_names},
                      pspec, pspec, P(),
                      {n: (pspec, pspec) for n in param_names}
                      if dgc_configs is not None else {}),
            out_specs=(P(), {n: P() for n in param_names},
                       {n: P() for n in buffer_names},
                       {n: (pspec, pspec) for n in param_names}
                       if dgc_configs is not None else {}),
            # vma tracking auto-psums grads of replicated params during
            # transpose — these optimizers exist to intercept the LOCAL
            # grad before any collective, so keep grads per-worker
            check_vma=False)

    def train_step(params, opt_state, buffers, x, y, key, lr):
        # the jitted program's name (``jit_train_step`` in a trace's module
        # line, ``jit(train_step)/`` in every op_name). It is part of the
        # compile-cache key, metadata is not: under its old name ``step``
        # a cache written before the program had scopes would hand back an
        # executable without them (PERF.md, PR 23).
        # batch stays dp-sharded via in_shardings; grads of replicated params
        # get psum'd across dp by SPMD automatically.
        # ZeRO-3 note: params arrive SHARDED (param_shards) and are NOT
        # gathered here — GSPMD inserts an all-gather at each weight's
        # use site, so peak live memory holds one layer's gathered
        # weights, not the full parameter set (the reference stages
        # per-segment broadcasts for the same reason,
        # sharding_optimizer.py segment logic). With recompute=True the
        # backward re-gathers instead of keeping gathered copies alive.
        if use_local_grads:
            comm_state = opt_state.get("__comm__", {})
            loss, grads, new_buffers, new_comm = local_grads_smapped(
                params, buffers, x, y, key, comm_state)
        else:
            (loss, new_buffers), grads = jax.value_and_grad(
                lambda p: forward_loss(p, buffers, x, y, key),
                has_aux=True)(params)
        if sharding_stage >= 2:
            # constrain grads to the shard layout -> reduce-scatter
            grads = {n: (grads[n] if has_mp[n] else
                         jax.lax.with_sharding_constraint(grads[n], zero_specs[n]))
                     for n in param_names}
        if grad_clip is not None:
            names = list(grads)
            with jax.named_scope("clip"):
                clipped = grad_clip.clip_arrays([grads[n] for n in names])
            grads = dict(zip(names, clipped))
        new_params, new_state = {}, {}
        with jax.named_scope("optimizer"):
            for name in param_names:
                g = grads[name].astype(params[name].dtype)
                if l1_coeff[name]:
                    g = g + l1_coeff[name] * jnp.sign(params[name])
                out = opt_update(params[name], g, lr, *opt_state[name],
                                 **hypers[name])
                new_params[name] = out[0]
                new_state[name] = tuple(out[1:])
        if use_local_grads and dgc_configs is not None:
            new_state["__comm__"] = new_comm
        if bad_step_guard:
            from ..resilience.badstep import select_tree, tree_nonfinite

            # grads (pre-update) + loss cover NaN/Inf from the forward
            # and backward; selecting the OLD state keeps the bad step a
            # no-op without breaking donation (one XLA program, buffer-
            # level aliasing still holds)
            bad = tree_nonfinite(loss) | tree_nonfinite(grads)
            new_params = select_tree(bad, params, new_params)
            new_state = select_tree(bad, opt_state, new_state)
            new_buffers = select_tree(bad, buffers, new_buffers)
            return loss, new_params, new_state, new_buffers, bad
        return loss, new_params, new_state, new_buffers

    def place_params():
        # Always copy: (a) cloned layers (TransformerEncoder-style
        # deepcopy) share init arrays, and device_put would alias them
        # into one buffer — donating the same buffer twice is an error;
        # (b) with donate=True the training params must not alias the
        # layer's own ._value arrays, or step 1 would delete the layer's
        # weights out from under eager readers.
        params = {}
        seen_ids = set()
        for n in param_names:
            src = params0[n]
            if donate or id(src) in seen_ids:
                src = jnp.array(src, copy=True)
            else:
                seen_ids.add(id(src))
            params[n] = jax.device_put(src, param_shards[n])
        return params

    def place_opt_state():
        opt_state = {}
        for n in param_names:
            st = optimizer._init_state(params0[n])
            if offload:
                opt_state[n] = tuple(
                    jax.device_put(a, s.with_memory_kind(_HOST_MEMORY_KIND)
                                   if a.ndim else s)
                    for a, s in zip(st, opt_state_specs[n]))
            elif shard_optimizer:
                opt_state[n] = tuple(
                    jax.device_put(a, _zero1_spec(a, mesh)) for a in st)
            else:
                opt_state[n] = tuple(jax.device_put(a, repl) for a in st)
        if use_local_grads and dgc_configs is not None:
            from . import comm_opt

            opt_state["__comm__"] = comm_opt.init_dgc_state(
                params0, mesh, data_axes)
        return opt_state

    init_fn = _spanned_init(place_params, place_opt_state)

    in_shardings = (
        param_shards,
        None,  # opt_state shardings propagate from the input arrays (init_fn)
        {n: repl for n in buffer_names},
        batch_shard,
        batch_shard,
        repl,
        repl,
    )
    out_shardings = (repl, param_shards, None, {n: repl for n in buffer_names})
    if bad_step_guard:
        out_shardings = out_shardings + (repl,)
    # donate params + opt_state: the step returns their replacements, so
    # XLA can update in place instead of holding both copies in HBM
    # (no-op on CPU backends, which don't implement donation)
    step_jit = jax.jit(train_step, in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1) if donate else ())

    # buffers thread through the step (BN stats / QAT scales update);
    # the latest values live in this cell and are synced back onto the
    # layer after every step so state_dict()/eval observe them
    buffers_cell = {"cur": {n: jnp.asarray(buffers0[n]) for n in buffer_names}}

    def _bounce(opt_state, kind):
        """Host<->device move of the non-scalar optimizer-state arrays
        (reference: sharding/offload_helper.py keeps optimizer state in
        host memory and copies it in around the update)."""
        return {
            n: tuple(
                jax.device_put(a, s.with_memory_kind(kind)) if a.ndim else a
                for a, s in zip(opt_state[n], opt_state_specs[n]))
            for n in opt_state}

    def step_fn(params, opt_state, x, y, key=None, lr=None):
        # one ``train.step`` span a call, a child span for each piece of
        # per-call host work: what the host does between two dispatches
        # is what a device gap is attributed to (PERF.md section 3)
        with tracing.span("train.step"):
            if key is None:
                key = jax.random.PRNGKey(0)
            if lr is None:
                with tracing.span("train.step.lr"):
                    lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
            if offload:
                with tracing.span("train.step.offload", to="device"):
                    opt_state = _bounce(opt_state, "device")
            with tracing.span("train.step.buffers_in",
                              buffers=len(buffer_names)):
                if buffer_names:
                    # pick up buffers loaded onto the layer since the last
                    # step (set_state_dict from a checkpoint etc.) — the
                    # cell only tracks values this step_fn wrote itself
                    _, live = layer.functional_state()
                    cur = buffers_cell["cur"]
                    if any(live.get(n) is not cur.get(n)
                           for n in buffer_names):
                        buffers_cell["cur"] = {n: jnp.asarray(live[n])
                                               for n in buffer_names}
            with tracing.span("train.step.call"):
                out = step_jit(
                    params, opt_state, buffers_cell["cur"], x, y, key, lr)
            loss, new_params, new_state, new_buffers = out[:4]
            if offload:
                with tracing.span("train.step.offload", to="host"):
                    new_state = _bounce(new_state, _HOST_MEMORY_KIND)
            with tracing.span("train.step.buffers_out"):
                buffers_cell["cur"] = new_buffers
                if buffer_names:
                    layer.load_functional_state(None, new_buffers)
        if bad_step_guard:
            return loss, new_params, new_state, out[4]
        return loss, new_params, new_state

    step_fn.jitted = step_jit  # AOT/lowering access (tests, memory checks)
    return step_fn, init_fn


def shard_batch(batch, mesh=None, axis=None):
    """Place a host array sharded on dim 0 over the data axes (dp+sharding).

    Multi-process (jax.distributed) runs follow the reference's trainer
    contract: each process passes its LOCAL batch and the global array is
    assembled across processes (global dim 0 = local * num_processes)."""
    mesh = mesh or topology.get_global_mesh()
    with tracing.span("spmd.shard_batch", devices=mesh.size) as sp:
        arr = (batch._value if isinstance(batch, Tensor)
               else jnp.asarray(np.asarray(batch)))
        sp.attrs["bytes"] = arr.nbytes
        if axis is None:
            axes = topology.data_axes(mesh)
            spec = P(axes) if axes else P()
        else:
            spec = P(axis)
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() > 1 and spec != P():
            local = np.asarray(arr)
            global_shape = ((local.shape[0] * jax.process_count(),)
                            + local.shape[1:])
            return jax.make_array_from_process_local_data(sharding, local,
                                                          global_shape)
        return jax.device_put(arr, sharding)


@tracing.spanned("train.build_step", builder="fsdp")
def build_fsdp_train_step(layers, loss_fn, optimizer, mesh=None,
                          recompute=True, amp_level="O0",
                          amp_dtype="bfloat16", donate=False):
    """ZeRO-3 with a scan-over-layers trunk (FSDP; reference:
    sharding_optimizer.py:180 per-segment broadcast staging).

    ``layers``: an nn.Sequential (or list of Layers) whose longest
    contiguous run of structurally-identical blocks becomes the scanned
    trunk. Trunk parameters are stacked [L, ...] and sharded over the
    dp+sharding axes; the scan body gathers ONE layer's weights
    (with_sharding_constraint -> all-gather at use), applies the block
    under jax.checkpoint, and lets the gathered copy die — peak live
    parameter memory is a single layer, not the model (the property the
    up-front gather of plain sharding_stage=3 cannot guarantee).

    Returns (step_fn, init_fn) with the build_train_step contract.
    Trunk params live under 'trunk.<name>' stacked; pre/post layers keep
    'pre.<i>.<name>' / 'post.<i>.<name>' replicated entries.
    """
    from .pipeline import split_pre_trunk_post, _functional_apply

    if hasattr(layers, "_sub_layers"):
        layer_list = [l for l in layers._sub_layers.values() if l is not None]
    else:
        layer_list = list(layers)
    for l in layer_list:
        if any(bn for _, sub in l.named_sublayers(include_self=True)
               for bn in sub._buffers):
            raise NotImplementedError(
                "build_fsdp_train_step does not thread layer buffers; "
                "use build_train_step(sharding_stage=3) for models with "
                "BatchNorm-style state")
    pre, trunk, post = split_pre_trunk_post(layer_list, 1)
    mesh = mesh or topology.get_global_mesh()
    data_axes = tuple(ax for ax in ("dp", "sharding")
                      if mesh.shape.get(ax, 1) > 1)
    world = 1
    for ax in data_axes:
        world *= mesh.shape[ax]
    template = trunk[0]
    L = len(trunk)
    amp_enabled = amp_level in ("O1", "O2")

    def _apply(layer, params, x, key):
        # buffer-free by the guard above, so params-only restore is safe
        if not amp_enabled:
            return _functional_apply(layer, params, x, key)
        from ..amp.auto_cast import auto_cast as _auto_cast

        saved = {n: p._value for n, p in layer.named_parameters()}
        try:
            with dispatch.trace_mode(), random_core.rng_guard(key), \
                    _auto_cast(enable=True, level=amp_level, dtype=amp_dtype):
                layer.load_functional_state(params)
                out = layer.forward(Tensor(x, stop_gradient=True))
                return out._value if isinstance(out, Tensor) else out
        finally:
            layer.load_functional_state(saved)

    # ---- param pytree: pre.<i>.<n> / trunk.<n> stacked [L,...] / post.<i>.<n>
    def _lp(l):
        return {n: p._value for n, p in l.named_parameters()}

    trunk_names = list(_lp(template))
    params0 = {}
    for i, l in enumerate(pre):
        for n, a in _lp(l).items():
            params0[f"pre.{i}.{n}"] = a
    for n in trunk_names:
        params0[f"trunk.{n}"] = jnp.stack([jnp.asarray(_lp(l)[n])
                                           for l in trunk])
    for i, l in enumerate(post):
        for n, a in _lp(l).items():
            params0[f"post.{i}.{n}"] = a
    param_names = list(params0)

    repl = NamedSharding(mesh, P())

    def _stacked_spec(arr):
        # shard a per-layer dim (never the stacked L dim) over data axes
        if world == 1:
            return repl
        for dim in range(1, arr.ndim):
            if arr.shape[dim] % world == 0:
                spec = [None] * arr.ndim
                spec[dim] = data_axes if len(data_axes) > 1 else data_axes[0]
                return NamedSharding(mesh, P(*spec))
        return repl

    param_shards = {}
    for n in param_names:
        param_shards[n] = (_stacked_spec(params0[n]) if n.startswith("trunk.")
                           else repl)

    def forward_loss(params, x, y, key):
        h = x
        for i, l in enumerate(pre):
            h = _apply(l, {n: params[f"pre.{i}.{n}"] for n in _lp(l)}, h,
                       jax.random.fold_in(key, 1000 + i))

        def body(h, xs):
            sliced, k = xs
            gathered = {n: jax.lax.with_sharding_constraint(a, repl)
                        for n, a in sliced.items()}
            return _apply(template, gathered, h, k), None

        if recompute:
            body = jax.checkpoint(body)
        stacked = {n: params[f"trunk.{n}"] for n in trunk_names}
        keys = jax.random.split(jax.random.fold_in(key, 7), L)
        h, _ = jax.lax.scan(body, h, (stacked, keys))
        for i, l in enumerate(post):
            h = _apply(l, {n: params[f"post.{i}.{n}"] for n in _lp(l)}, h,
                       jax.random.fold_in(key, 2000 + i))
        return loss_fn(h, y)

    hypers = optimizer._hypers()
    l1_coeff = type(optimizer)._take_l1(hypers)
    opt_update = type(optimizer)._update
    grad_clip = optimizer._grad_clip
    batch_shard = NamedSharding(mesh, P(data_axes)) if data_axes else repl

    def step(params, opt_state, x, y, key, lr):
        with topology.tracing_for(mesh):
            loss, grads = jax.value_and_grad(
                lambda p: forward_loss(p, x, y, key))(params)
        # keep grads in the shard layout -> reduce-scatter, ZeRO-2 style
        grads = {n: jax.lax.with_sharding_constraint(g, param_shards[n])
                 for n, g in grads.items()}
        if grad_clip is not None:
            names = list(grads)
            clipped = grad_clip.clip_arrays([grads[n] for n in names])
            grads = dict(zip(names, clipped))
        new_params, new_state = {}, {}
        for n in param_names:
            g = grads[n].astype(params[n].dtype)
            if l1_coeff:
                g = g + l1_coeff * jnp.sign(params[n])
            out = opt_update(params[n], g, lr, *opt_state[n], **hypers)
            new_params[n] = out[0]
            new_state[n] = tuple(out[1:])
        return loss, new_params, new_state

    step_jit = jax.jit(
        step,
        in_shardings=(param_shards, None, batch_shard, batch_shard,
                      repl, repl),
        out_shardings=(repl, param_shards, None),
        donate_argnums=(0, 1) if donate else ())

    def place_params():
        return {n: jax.device_put(params0[n], param_shards[n])
                for n in param_names}

    def place_opt_state():
        opt_state = {}
        for n in param_names:
            st = optimizer._init_state(np.asarray(params0[n]))
            opt_state[n] = tuple(
                jax.device_put(a, _stacked_spec(a)
                               if n.startswith("trunk.") else repl)
                for a in st)
        return opt_state

    init_fn = _spanned_init(place_params, place_opt_state)

    def step_fn(params, opt_state, x, y, key=None, lr=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        if lr is None:
            lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        return step_jit(params, opt_state, x, y, key, lr)

    step_fn.jitted = step_jit
    return step_fn, init_fn
