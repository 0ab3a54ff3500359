"""Central op dispatch — the single "op registry" serving both execution modes.

The reference unifies static + dygraph execution through one C++ operator
registry (reference: paddle/fluid/framework/op_registry.h:273, OpInfoMap
op_info.h:131; dygraph fast path pybind/op_function_generator.cc:497).
Here every op is one *pure JAX function* ``fn(*arrays, **static_kwargs)``
and this module is the unification point:

- **Eager (dygraph)**: ``apply_op`` unwraps Tensors, runs the op through a
  cached ``jax.jit`` (the ``core.ops.*`` fast-path analog — compile once
  per (op, shapes, statics), then C++-speed dispatch), and records a tape
  node for autograd.
- **Traced (to_static / jitted train step / pjit)**: inputs are JAX
  tracers; the op function is invoked directly so it inlines into the
  enclosing XLA computation. No tape is recorded — gradients come from
  functional ``jax.grad`` over the whole step, which is how the MXU gets
  one fused backward program instead of per-op launches.

Convention: positional args are array-likes (Tensor / jax.Array / numpy /
scalar / None); everything static (axes, strides, flags) must be a keyword
argument and hashable-after-normalisation.
"""
import contextvars
import functools
import weakref

import jax
import numpy as np

from . import flags

# ---------------------------------------------------------------- mode state

_TAPE_ENABLED = contextvars.ContextVar("tape_enabled", default=True)
AMP_HOOK = None  # installed by paddle_tpu.amp (per-op cast policy)
PROGRAM_HOOK = None  # installed by paddle_tpu.static program_guard (op recorder)
_IN_TRACE = contextvars.ContextVar("in_trace", default=False)


def tape_enabled():
    return _TAPE_ENABLED.get() and not _IN_TRACE.get()


class no_grad_ctx:
    """paddle.no_grad — disables tape recording (dygraph only)."""

    def __enter__(self):
        self._token = _TAPE_ENABLED.set(False)
        return self

    def __exit__(self, *exc):
        _TAPE_ENABLED.reset(self._token)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with no_grad_ctx():
                return fn(*args, **kwargs)

        return wrapper


class enable_grad_ctx:
    def __enter__(self):
        self._token = _TAPE_ENABLED.set(True)
        return self

    def __exit__(self, *exc):
        _TAPE_ENABLED.reset(self._token)
        return False


class trace_mode:
    """Mark that we are inside a jax trace (to_static / functional step)."""

    def __enter__(self):
        self._token = _IN_TRACE.set(True)
        return self

    def __exit__(self, *exc):
        _IN_TRACE.reset(self._token)
        return False


def in_trace():
    return _IN_TRACE.get()


# ---------------------------------------------------------------- utilities


def hashable(obj):
    """Normalise static kwargs into a hashable cache key.

    Type checks come before any truthiness test: ``not obj`` on an
    ndarray raises, so the old ``if not obj and isinstance(obj, dict)``
    fast path crashed on array-valued statics (tracelint TPU102 audits
    them; found by tests/test_tracelint.py)."""
    if isinstance(obj, dict):
        if not obj:
            return ()  # fast path: the common no-static-kwargs op
        return tuple(sorted((k, hashable(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(hashable(o) for o in obj)
    if isinstance(obj, set):
        return tuple(sorted(hashable(o) for o in obj))
    if isinstance(obj, np.dtype):
        return obj.name
    return obj


_FWD_CACHE = {}

# ---------------------------------------------------------------- op registry
#
# The OpInfoMap analog, now introspectable: def_op registrations land in
# OP_REGISTRY; ops that flow through apply_op directly (the dominant
# in-tree idiom) are observed on first dispatch into OPS_SEEN with the
# static-kwarg names used at that call site. paddle_tpu.analysis's
# registry passes (tools/tracelint.py --registry) audit both against the
# dispatch contract documented at the top of this module.

OP_REGISTRY = {}  # name -> def_op api wrapper (api.raw_fn is the pure fn)
# name -> (weakref-or-fn, static kwarg names at first dispatch). Weakly
# referenced so observation never pins a closure op (to_static pure_fns
# close over whole Layers) past its owner's lifetime.
OPS_SEEN = {}


def ops_seen_live():
    """Resolve OPS_SEEN to {name: (fn, kwarg_names)}, dropping dead refs."""
    out = {}
    for name, (ref, kwnames) in list(OPS_SEEN.items()):
        fn = ref() if isinstance(ref, weakref.ref) else ref
        if fn is None:
            del OPS_SEEN[name]
        else:
            out[name] = (fn, kwnames)
    return out


def fn_key(name, fn):
    """Stable cache key for an op function.

    Op implementations are closures/lambdas recreated per API call, so
    keying on identity would recompile every step and leak cache entries.
    The dispatch convention (all statics in kwargs, closures capture
    nothing) makes (op name, module, qualname) a correct stable key; ops
    that DO capture state (to_static programs, recompute segments) pass a
    discriminating uid kwarg.
    """
    q = getattr(fn, "__qualname__", None)
    return (name, getattr(fn, "__module__", None),
            q if q is not None else repr(fn))


def evict_ops(name):
    """Drop cached jits whose op name equals ``name`` (exact match — a
    prefix match would collide across uids, e.g. _u2 vs _u20).

    For ops keyed with a per-instance uid (state-capturing closures like
    HeterPSEmbedding): the owner calls this on teardown so the cached
    jit does not pin its captured state (PS client, tables) forever."""
    dead = [k for k in _FWD_CACHE
            if isinstance(k[0], tuple) and k[0][0] == name]
    for k in dead:
        del _FWD_CACHE[k]
    # the observed-op registry holds the same fn reference — drop it too
    # or the captured state outlives the teardown it was evicted for
    OPS_SEEN.pop(name, None)


def jitted(fn, kwargs, name=None):
    """Cached jax.jit of fn with static kwargs closed over."""
    key = (fn_key(name, fn) if name is not None else fn, hashable(kwargs))
    got = _FWD_CACHE.get(key)
    if got is None:
        if kwargs:
            got = jax.jit(lambda *a: fn(*a, **kwargs))
        else:
            got = jax.jit(fn)
        _FWD_CACHE[key] = got
    return got


def _is_tracer(v):
    return isinstance(v, jax.core.Tracer)


def _check_nan_inf(name, arrays):
    import jax.numpy as jnp

    for a in arrays:
        if hasattr(a, "dtype") and np.issubdtype(np.dtype(a.dtype), np.inexact):
            if bool(jnp.any(~jnp.isfinite(a))):
                from . import errors

                raise errors.PreconditionNotMetError(
                    f"NaN/Inf detected in output of op {name!r} "
                    "(FLAGS_check_nan_inf; reference nan_inf_utils_detail.cc analog)"
                )


# ---------------------------------------------------------------- dispatch


_HOT = None  # (Tensor, tape_mod) resolved once — import machinery is
# measurable per-op overhead on the eager path


def _hot_mods():
    global _HOT
    if _HOT is None:
        from . import tape as tape_mod
        from . import tensor as tensor_mod

        _HOT = (tensor_mod.Tensor, tape_mod)
    return _HOT


def apply_op(name, fn, *args, **kwargs):
    """Execute one op. Returns Tensor or tuple-of-Tensor mirroring fn's output."""
    Tensor, tape_mod = _hot_mods()

    if name not in OPS_SEEN:  # first dispatch only — hot path stays one lookup
        try:
            ref = weakref.ref(fn)
        except TypeError:  # not weakref-able (e.g. builtins, partials)
            ref = fn
        OPS_SEEN[name] = (ref, tuple(sorted(kwargs)))

    arrays = []
    diff_argnums = []
    in_tensors = []
    requires_grad = False
    record = tape_enabled()
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            v = a._value
            arrays.append(v)
            if record and not a.stop_gradient and _is_float(v):
                diff_argnums.append(i)
                in_tensors.append(a)
                requires_grad = True
        else:
            arrays.append(a)

    if AMP_HOOK is not None:
        arrays = AMP_HOOK(name, arrays)

    traced = _IN_TRACE.get() or any(_is_tracer(v) for v in arrays if v is not None)

    if traced:
        out = fn(*arrays, **kwargs)
        return _wrap_outputs(out, requires_grad=not _all_stop(args, Tensor), node=None)

    if flags.flag_value("eager_jit_ops"):
        out = jitted(fn, kwargs, name=name)(*arrays)
    else:
        out = fn(*arrays, **kwargs)

    if flags.flag_value("check_nan_inf"):
        _check_nan_inf(name, out if isinstance(out, (tuple, list)) else (out,))

    node = None
    if requires_grad:
        node = tape_mod.Node(name, fn, kwargs, tuple(arrays), tuple(diff_argnums), in_tensors)

    wrapped = _wrap_outputs(out, requires_grad=requires_grad, node=node)
    if PROGRAM_HOOK is not None:
        outs_list = list(wrapped) if isinstance(wrapped, tuple) else [wrapped]
        PROGRAM_HOOK.record(fn, kwargs, args, outs_list)
    return wrapped


def _is_float(v):
    try:
        return np.issubdtype(np.dtype(v.dtype), np.floating) or str(v.dtype) == "bfloat16"
    except Exception:
        return isinstance(v, float)


def _all_stop(args, Tensor):
    for a in args:
        if isinstance(a, Tensor) and not a.stop_gradient:
            return False
    return True


def _wrap_outputs(out, requires_grad, node):
    Tensor = _hot_mods()[0]

    if isinstance(out, (tuple, list)):
        outs = []
        for i, o in enumerate(out):
            t = Tensor(o, stop_gradient=not requires_grad)
            if node is not None:
                t._node = node
                t._out_idx = i
            outs.append(t)
        if node is not None:
            node.set_outputs(outs, multi=True)
        return tuple(outs)
    t = Tensor(out, stop_gradient=not requires_grad)
    if node is not None:
        t._node = node
        t._out_idx = 0
        node.set_outputs([t], multi=False)
    return t


def def_op(name, fn):
    """Define a user-facing op from a pure jax function (the REGISTER_OPERATOR analog)."""

    @functools.wraps(fn)
    def api(*args, **kwargs):
        return apply_op(name, fn, *args, **kwargs)

    api.__name__ = name
    api.raw_fn = fn
    OP_REGISTRY[name] = api
    return api
