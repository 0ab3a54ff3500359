"""jaxpr recompilation-hazard passes (TPU101–TPU104).

Where the AST passes inspect *source*, these inspect the *traced
program*: ``jax.make_jaxpr`` gives the closed jaxpr without compiling or
executing, and properties of that jaxpr predict TPU goodput sinks —
constants baked into HLO (re-uploaded per compile, per donated buffer
lost), weak-typed outputs (silent retrace per Python-scalar flavour),
unhashable statics (every dispatch misses the ``core/dispatch.py`` jit
cache), and collectives whose ``axis_name`` cannot resolve on the mesh
that will execute the program (a guaranteed trace-time crash on the pod,
caught here on CPU first).
"""
import numpy as np

import jax
from jax.extend import core as jax_core

from .diagnostics import Diagnostic

# Constants below this many bytes are noise (scalars, iota, eps tables).
DEFAULT_CONST_THRESHOLD = 256 * 1024


def _loc_of(fn):
    code = getattr(fn, "__code__", None)
    if code is None:
        inner = getattr(fn, "__wrapped__", None)
        code = getattr(inner, "__code__", None)
    if code is None:
        return "<callable>", 0
    return code.co_filename, code.co_firstlineno


def make_jaxpr_of(fn, *example_args, **example_kwargs):
    """Trace fn to a ClosedJaxpr without executing it."""
    return jax.make_jaxpr(lambda *a: fn(*a, **example_kwargs))(*example_args)


def check_constants(closed, filename="<trace>", line=0, func="",
                    threshold=DEFAULT_CONST_THRESHOLD):
    """TPU101 — closure-captured arrays inlined into the program."""
    diags = []
    for const in getattr(closed, "consts", ()):
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            arr = np.asarray(const)
            nbytes = arr.nbytes
        if nbytes >= threshold:
            shape = tuple(getattr(const, "shape", ()) or ())
            dtype = getattr(const, "dtype", type(const).__name__)
            diags.append(Diagnostic(
                code="TPU101",
                message=(f"constant of {nbytes / 1e6:.2f} MB "
                         f"(shape {shape}, {dtype}) is closure-captured and "
                         "baked into the compiled program"),
                filename=filename, line=line, func=func))
    return diags


def check_weak_types(closed, filename="<trace>", line=0, func=""):
    """TPU103 — weak-typed outputs retrace on the next scalar flavour."""
    diags = []
    for i, aval in enumerate(closed.out_avals):
        if getattr(aval, "weak_type", False):
            diags.append(Diagnostic(
                code="TPU103",
                message=(f"output {i} has weak type {aval.dtype}; a Python "
                         "scalar reached the output, so calls with a "
                         "different scalar flavour retrace"),
                filename=filename, line=line, func=func))
    return diags


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value):
    if isinstance(value, jax_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax_core.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def collective_axis_names(closed):
    """All collective axis names appearing in the jaxpr (psum 'axes',
    ppermute/all_gather 'axis_name', sorted for stable output)."""
    names = set()
    for eqn in _iter_eqns(closed.jaxpr):
        for key in ("axes", "axis_name"):
            v = eqn.params.get(key)
            if v is None:
                continue
            if isinstance(v, (list, tuple)):
                names.update(x for x in v if isinstance(x, str))
            elif isinstance(v, str):
                names.add(v)
    return sorted(names)


def check_collectives(closed, mesh_axis_names, filename="<trace>", line=0,
                      func=""):
    """TPU104 — axis names must resolve on the mesh that will run this."""
    mesh_axes = set(mesh_axis_names)
    diags = []
    for name in collective_axis_names(closed):
        if name not in mesh_axes:
            diags.append(Diagnostic(
                code="TPU104",
                message=(f"collective uses axis_name {name!r} but the active "
                         f"mesh only has axes {sorted(mesh_axes)}"),
                filename=filename, line=line, func=func))
    return diags


def check_static_kwargs(kwargs, filename="<call>", line=0, func="",
                        code="TPU102"):
    """TPU102 — statics must normalise hashable through dispatch.hashable
    or every call misses the jit cache (or crashes the dict lookup)."""
    from ..core import dispatch

    diags = []
    for key, value in sorted(kwargs.items()):
        # the array case first: arrays are also unhashable, but deserve
        # the actionable retrace message rather than the generic one
        if isinstance(value, (np.ndarray, jax.Array)):
            diags.append(Diagnostic(
                code=code,
                message=(f"static kwarg {key} is an array; array-valued "
                         "statics retrace on every distinct value"),
                filename=filename, line=line, func=func))
            continue
        try:
            hash(dispatch.hashable(value))
        except (TypeError, ValueError):  # ValueError: ambiguous-truth arrays
            # inside dict/set normalisation (sorted() comparisons)
            diags.append(Diagnostic(
                code=code,
                message=(f"static kwarg {key}={type(value).__name__!s}(...) "
                         "does not normalise to a hashable cache key"),
                filename=filename, line=line, func=func))
    return diags


def check_function(fn, example_args=(), static_kwargs=None, mesh=None,
                   const_threshold=DEFAULT_CONST_THRESHOLD):
    """Run every jaxpr pass over one callable with example inputs.

    ``mesh=None`` resolves the active global mesh when one is initialised
    (collective checks are skipped otherwise). Trace failures are the
    AST passes' and dy2static hook's domain — they propagate.
    """
    filename, line = _loc_of(fn)
    func = getattr(fn, "__name__", "")
    static_kwargs = dict(static_kwargs or {})
    diags = check_static_kwargs(static_kwargs, filename, line, func)
    closed = make_jaxpr_of(fn, *example_args, **static_kwargs)
    diags += check_constants(closed, filename, line, func,
                             threshold=const_threshold)
    diags += check_weak_types(closed, filename, line, func)
    axis_names = None
    if mesh is not None:
        axis_names = mesh.axis_names
    else:
        from ..distributed import topology

        # only check against an explicitly-configured mesh; the implicit
        # single-axis default would flag every model-parallel program
        if topology._GLOBAL_MESH is not None:
            axis_names = topology._GLOBAL_MESH.axis_names
    if axis_names is not None:
        diags += check_collectives(closed, axis_names, filename, line, func)
    return diags
