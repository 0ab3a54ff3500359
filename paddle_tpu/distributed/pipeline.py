"""Pipeline-parallel TRAINING as one differentiable SPMD program.

Reference analogs: the static PipelineOptimizer program split +
send_v2/recv_v2 insertion (python/paddle/fluid/optimizer.py:3718,4269) and
the SectionWorker F-then-B / 1F1B schedules
(paddle/fluid/framework/section_worker.cc:116-160).

TPU-native design: no per-stage processes, no P2P ops. The homogeneous
trunk's per-layer weights are STACKED on a leading axis sharded over the
'pp' mesh axis; a ``shard_map`` body runs ``lax.scan`` over
(num_micro + num_stages - 1) ticks, each tick = receive the activation
from the left neighbor via ``ppermute``, apply the local stage, emit
right. ``jax.grad`` through scan+ppermute yields the transposed
(backward) pipeline automatically — XLA schedules the resulting wave; the
explicit 1F1B loop of section_worker.cc is subsumed by the compiler
schedule. Embedding/head ("pre"/"post") layers run outside the pipelined
region on their natural dp sharding.

Memory note: whole-graph grad gives a GPipe-style schedule (activations
of all live ticks retained); pass ``recompute=True`` to rematerialise
each stage application in the backward (jax.checkpoint), the analog of
the reference's recompute+pipeline composition.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import dispatch, random as random_core
from ..core.tensor import Tensor
from ..obs import tracing
from . import topology


def _functional_apply(layer, params, x, key):
    """Run layer.forward(x) as a pure function of `params` (same
    mutation-bracket trick as spmd.build_train_step). Buffers are
    snapshotted and restored too: BatchNorm-style layers write traced
    stats into their buffers during a traced forward, and those tracers
    must not outlive the trace."""
    saved = {n: p._value for n, p in layer.named_parameters()}
    _, saved_b = layer.functional_state()
    try:
        with dispatch.trace_mode(), random_core.rng_guard(key):
            layer.load_functional_state(params)
            out = layer.forward(Tensor(x, stop_gradient=True))
            return out._value if isinstance(out, Tensor) else out
    finally:
        layer.load_functional_state(saved, saved_b)


def _layer_signature(layer):
    """Structural identity for homogeneity: class + param shapes/dtypes."""
    return (type(layer).__name__,
            tuple((n, tuple(p.shape), str(np.dtype(p.dtype)))
                  for n, p in layer.named_parameters()))


def split_pre_trunk_post(layers, num_stages):
    """Find the longest contiguous run of structurally-identical layers
    whose length divides into num_stages equal segments. Returns
    (pre_layers, trunk_layers, post_layers)."""
    n = len(layers)
    sigs = [_layer_signature(l) for l in layers]
    best = None  # (length, start)
    i = 0
    while i < n:
        j = i
        while j < n and sigs[j] == sigs[i]:
            j += 1
        run = j - i
        # largest multiple of num_stages that fits this run, right-aligned
        usable = (run // num_stages) * num_stages
        if usable >= num_stages and (best is None or usable > best[0]):
            best = (usable, i + (run - usable))
        i = j
    if best is None:
        raise ValueError(
            f"no contiguous run of {num_stages}+ structurally-identical "
            f"layers found; pipeline needs a homogeneous trunk")
    length, start = best
    return (list(layers[:start]), list(layers[start:start + length]),
            list(layers[start + length:]))


@tracing.spanned("train.build_step", builder="pipeline")
def build_pipeline_train_step(pre_layers, trunk_layers, post_layers, loss_fn,
                              optimizer, mesh=None, num_micro=None,
                              recompute=False, donate=True,
                              amp_level="O0", amp_dtype="bfloat16"):
    """Compile a pipeline-parallel training step.

    - pre_layers/post_layers: lists of Layers applied outside the pipelined
      region (replicated weights, dp-sharded activations).
    - trunk_layers: homogeneous list (len divisible by pp) pipelined over
      the 'pp' mesh axis.
    - loss_fn(out_array, label_array) -> scalar (pure jnp).

    Returns (step_fn, init_fn):
      init_fn() -> (params, opt_state) with 'stages' leaves sharded P('pp')
      step_fn(params, opt_state, x, y, key, lr) -> (loss, params, opt_state)

    amp_level "O1"/"O2" (the reference's amp+pipeline meta-optimizer
    composition): pre/post layers trace under ``paddle.amp.auto_cast``
    (per-op white/black lists, like spmd.build_train_step); the
    pipelined trunk runs each STAGE interior in pure ``amp_dtype`` via
    explicit casts at the stage boundary — per-op converts inside the
    manual shard_map region trip an XLA-CPU bf16-legalization CHECK,
    and a whole-stage cast is the better TPU schedule anyway (one
    convert per boundary, not per op). Activations cross stage
    boundaries in the carry dtype (f32).
    """
    if amp_level not in ("O0", "O1", "O2"):
        raise ValueError(f"amp_level must be 'O0'|'O1'|'O2', "
                         f"got {amp_level!r}")
    amp_enabled = amp_level in ("O1", "O2")
    if amp_dtype in ("bfloat16", "bf16"):
        amp_jdtype = jnp.bfloat16
    elif amp_dtype in ("float16", "fp16"):
        amp_jdtype = jnp.float16
    else:
        raise ValueError(f"amp_dtype must be bfloat16/bf16/float16/fp16, "
                         f"got {amp_dtype!r}")
    mesh = mesh or topology.get_global_mesh()
    num_stages = int(mesh.shape.get("pp", 1))
    L = len(trunk_layers)
    if num_stages < 1 or L % num_stages != 0:
        raise ValueError(f"{L} trunk layers not divisible into "
                         f"{num_stages} pipeline stages")
    lps = L // num_stages  # layers per stage
    num_micro = int(num_micro or num_stages)
    template = trunk_layers[0]

    # ---- flatten params: pre.<i>.<n>, stages.<n> (stacked [S, lps, ...]),
    # post.<i>.<n>
    def _layer_params(layer):
        return {n: p._value for n, p in layer.named_parameters()}

    pre_p0 = {f"pre.{i}.{n}": a for i, l in enumerate(pre_layers)
              for n, a in _layer_params(l).items()}
    post_p0 = {f"post.{i}.{n}": a for i, l in enumerate(post_layers)
               for n, a in _layer_params(l).items()}
    trunk_names = list(_layer_params(template))
    # tensor-parallel composition (dp x pp x mp, the reference's hybrid
    # stretch config): per-param mp_spec from the Megatron layers rides
    # BEHIND the [stage, layer] stacking dims; the 'mp' axis stays an
    # AUTO axis of the shard_map so GSPMD partitions the stage interior
    # and inserts the Megatron collectives, while 'pp' stays manual for
    # the explicit ppermute schedule.
    trunk_mp_spec = {n: getattr(p, "mp_spec", None)
                     for n, p in template.named_parameters()}
    stages_p0 = {}
    for n in trunk_names:
        per_layer = [_layer_params(l)[n] for l in trunk_layers]
        stacked = jnp.stack(per_layer).reshape(
            (num_stages, lps) + per_layer[0].shape)
        stages_p0[f"stages.{n}"] = stacked
    params0 = {**pre_p0, **stages_p0, **post_p0}
    param_names = list(params0)

    repl = NamedSharding(mesh, P())
    data_axes = tuple(ax for ax in ("dp", "sharding")
                      if mesh.shape.get(ax, 1) > 1)
    # sequence parallelism (pp x sp long context): with sp on the mesh,
    # activations are [B, S, ...] with the SEQ dim sharded over sp;
    # stage interiors call ring_attention_in_shard_map (sp is a manual
    # axis of the trunk shard_map alongside pp). data_p is THE one
    # activation partition spec — batch placement and the trunk's
    # in_spec both use it.
    sp_n = int(mesh.shape.get("sp", 1))
    if sp_n > 1:
        data_p = P(data_axes if data_axes else None, "sp")
    else:
        data_p = P(data_axes) if data_axes else P()
    batch_spec = NamedSharding(mesh, data_p)

    def _place_input(arr):
        """Per-array placement: the sp seq sharding applies only to
        arrays that HAVE a sharded seq dim (rank-1 labels etc. keep the
        plain data-axes layout)."""
        if sp_n > 1 and (arr.ndim < 2 or arr.shape[1] % sp_n != 0):
            return jax.device_put(
                arr, NamedSharding(mesh, P(data_axes) if data_axes
                                   else P()))
        return jax.device_put(arr, batch_spec)

    def _stage_sharding(name):
        spec = trunk_mp_spec.get(name)
        if spec:
            return NamedSharding(mesh, P("pp", None, *spec))
        return NamedSharding(mesh, P("pp"))

    shardings = {n: (_stage_sharding(n[len("stages."):])
                     if n.startswith("stages.") else repl)
                 for n in param_names}

    def _stage_apply(stage_params, x, key):
        """Apply this stage's lps layers (scan over the stacked dim).

        amp: the stage interior runs in pure ``amp_dtype`` via explicit
        casts of params + activation at the stage boundary (the per-op
        auto_cast hook is suspended inside the manual trunk region —
        its convert-per-op pattern trips an XLA-CPU legalization CHECK;
        O1's white/black lists still govern pre/post layers)."""
        keys = jax.random.split(key, lps)
        if amp_enabled:
            stage_params = jax.tree.map(
                lambda a: a.astype(amp_jdtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                stage_params)
            x = x.astype(amp_jdtype)

        def per_layer(h, xs):
            p_layer, k = xs
            return _functional_apply(template, p_layer, h, k), None

        out, _ = jax.lax.scan(per_layer, x, (stage_params, keys))
        return out

    if recompute:
        _stage_apply = jax.checkpoint(_stage_apply)

    # every axis the batch shards over (dp, sharding, AND the seq-dim
    # sp) varies the carry; missing one trips the scan's
    # varying-manual-axes check
    shard_axes = ("pp",) + data_axes + (("sp",) if sp_n > 1 else ())

    def body(stage_params_local, h_local, key):
        # stage_params_local: [1, lps, ...] slices; h_local: [B_loc, ...]
        stage = jax.lax.axis_index("pp")
        p_stage = jax.tree.map(lambda a: a[0], stage_params_local)
        b_loc = h_local.shape[0]
        m_shape = (num_micro, b_loc // num_micro) + h_local.shape[1:]
        micro = h_local.reshape(m_shape)
        micro = jax.lax.pcast(micro, ("pp",), to="varying")
        carry_in = jax.lax.pcast(jnp.zeros(m_shape[1:], h_local.dtype),
                                 shard_axes, to="varying")
        outputs = jax.lax.pcast(jnp.zeros(m_shape, h_local.dtype),
                                shard_axes, to="varying")
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(state, t):
            carry, outputs = state
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < num_micro)
            x_in = jnp.where(stage == 0,
                             micro[jnp.clip(t, 0, num_micro - 1)], carry)
            k = jax.random.fold_in(jax.random.fold_in(key, t), stage)
            # the carry dtype is fixed across ticks: under amp the stage
            # emits amp_dtype, which must cast back at the boundary
            y = _stage_apply(p_stage, x_in, k).astype(x_in.dtype)
            y = jnp.where(active, y, jnp.zeros_like(y))
            is_last = stage == num_stages - 1
            out_idx = jnp.clip(mb_idx, 0, num_micro - 1)
            outputs = jnp.where(active & is_last,
                                outputs.at[out_idx].set(y), outputs)
            carry_next = jax.lax.ppermute(y, "pp", perm)
            return (carry_next, outputs), None

        (carry, outputs), _ = jax.lax.scan(
            tick, (carry_in, outputs),
            jnp.arange(num_micro + num_stages - 1))
        outputs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outputs,
                      jnp.zeros_like(outputs)), "pp")
        return outputs.reshape((b_loc,) + outputs.shape[2:])

    h_in_spec = data_p
    # only pp (the explicit ppermute schedule), the data axes, and sp
    # (the stage-interior ring) are MANUAL; every other mesh axis (mp,
    # ep, ...) stays auto so GSPMD partitions the stage interior via
    # the layers' sharding annotations (Megatron tensor parallel / MoE
    # expert parallel inside pipeline stages). For meshes with no such
    # axis this is identical to all-manual.
    manual_axes = frozenset(shard_axes)
    trunk_fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("pp"), h_in_spec, P()),
        out_specs=h_in_spec, axis_names=manual_axes)

    def forward_loss(params, x, y, key):
        from ..amp.auto_cast import auto_cast as _auto_cast
        from ..amp.auto_cast import suspend_auto_cast

        with _auto_cast(enable=amp_enabled, level=amp_level,
                        dtype=amp_dtype):
            h = x
            kpre = jax.random.fold_in(key, 10_000)
            for i, layer in enumerate(pre_layers):
                lp = {n: params[f"pre.{i}.{n}"]
                      for n, _ in layer.named_parameters()}
                h = _functional_apply(layer, lp, h,
                                      jax.random.fold_in(kpre, i))
            if amp_enabled:
                # enforce the documented invariant: the trunk carry and
                # ppermute traffic run in f32 regardless of what dtype
                # the last pre layer emitted under the hook
                h = h.astype(jnp.float32)
            stage_params = {n: params[f"stages.{n}"] for n in trunk_names}
            with suspend_auto_cast():
                h = trunk_fn(stage_params, h, key)
            kpost = jax.random.fold_in(key, 20_000)
            for i, layer in enumerate(post_layers):
                lp = {n: params[f"post.{i}.{n}"]
                      for n, _ in layer.named_parameters()}
                h = _functional_apply(layer, lp, h,
                                      jax.random.fold_in(kpost, i))
            return loss_fn(h, y)

    hypers = optimizer._hypers()
    l1_coeff = type(optimizer)._take_l1(hypers)
    opt_update = type(optimizer)._update
    grad_clip = optimizer._grad_clip

    def step(params, opt_state, x, y, key, lr):
        loss, grads = jax.value_and_grad(
            lambda p: forward_loss(p, x, y, key))(params)
        if grad_clip is not None:
            names = list(grads)
            clipped = grad_clip.clip_arrays([grads[n] for n in names])
            grads = dict(zip(names, clipped))
        new_params, new_state = {}, {}
        for name in param_names:
            g = grads[name].astype(params[name].dtype)
            if l1_coeff:
                g = g + l1_coeff * jnp.sign(params[name])
            out = opt_update(params[name], g, lr, *opt_state[name], **hypers)
            new_params[name] = out[0]
            new_state[name] = tuple(out[1:])
        return loss, new_params, new_state

    # ZeRO-1 x pipeline (reference: sharding+pipeline meta-optimizer
    # composition): optimizer-state arrays additionally shard their
    # first divisible dim over the dp/sharding axes — stage states
    # behind the [stage, layer] stacking dims, pre/post states exactly
    # like spmd's ZeRO-1 (same _zero1_spec). Elementwise updates keep
    # the layout: the memory win of sharding_optimizer.py stage 1.
    from .spmd import _spanned_init, _zero1_spec

    zero_axes = tuple(ax for ax in ("dp", "sharding")
                      if mesh.shape.get(ax, 1) > 1)

    def _opt_state_sharding(name, a):
        if np.ndim(a) != np.ndim(params0[name]):
            return repl  # scalar states (step counters)
        if not zero_axes:
            return shardings[name]
        if name.startswith("stages."):
            return _zero1_spec(a, mesh, axes=zero_axes, start=2,
                               prefix=tuple(shardings[name].spec))
        return _zero1_spec(a, mesh, axes=zero_axes)

    def place_params():
        return {n: jax.device_put(params0[n], shardings[n])
                for n in param_names}

    def place_opt_state():
        opt_state = {}
        for n in param_names:
            st = optimizer._init_state(params0[n])
            # scalar states (step counters) stay replicated; stage-shaped
            # states inherit the stacked pp sharding (+ ZeRO-1 sharding)
            opt_state[n] = tuple(
                jax.device_put(a, _opt_state_sharding(n, a)) for a in st)
        return opt_state

    init_fn = _spanned_init(place_params, place_opt_state)

    in_shardings = (shardings, None, batch_spec, None, repl, repl)
    out_shardings = (repl, shardings, None)
    step_jit = jax.jit(step, in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1) if donate else ())

    def step_fn(params, opt_state, x, y, key=None, lr=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        if lr is None:
            lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        # inputs may arrive as committed single-device arrays (eager
        # Tensors); place them on the data axes explicitly
        x = jax.device_put(jnp.asarray(x), batch_spec)
        y = _place_input(jnp.asarray(y))
        return step_jit(params, opt_state, x, y, key, lr)

    step_fn.jitted = step_jit  # AOT access (schedule/memory introspection)
    step_fn.schedule = schedule_stats(num_stages, num_micro)
    return step_fn, init_fn


def schedule_stats(num_stages, num_micro):
    """Analytic schedule properties of the ppermute-scan pipeline.

    The scan runs exactly ``num_micro + num_stages - 1`` ticks; each tick
    every stage is busy except during ramp-up/drain, giving the classic
    GPipe bubble fraction (S-1)/(M+S-1) (reference:
    section_worker.cc:135 startup_steps = num_stages - stage_id - 1 has
    the same ramp geometry). Raising num_micro amortises the bubble;
    recompute bounds activation memory per stage at one microbatch.
    """
    ticks = num_micro + num_stages - 1
    return {
        "num_stages": int(num_stages),
        "num_micro": int(num_micro),
        "ticks": int(ticks),
        "bubble_fraction": float((num_stages - 1) / ticks),
    }
