"""jit.save / jit.load (reference: dygraph/jit.py save, dygraph/io.py
TranslatedLayer; format: save_inference_model's ProgramDesc+params).

TPU-native format: serialized StableHLO (jax.export) + numpy params +
a JSON signature — the portable compiled-program analog. A model that
cannot be exported at its input spec fails the save.
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dispatch
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..serialize.export import (deserialize_exported, model_fingerprint,
                                serialize_exported)
from .static_function import StaticFunction, _flatten_tensors


def _build_input_specs(input_spec, polymorphic):
    """Turn InputSpec/Tensor entries into jax ShapeDtypeStructs. With
    `polymorphic`, None/-1 dims become jax.export symbolic dims, so the
    exported module accepts ANY size there — the enabler for the
    serving engine's shape-bucket batching. Returns
    (candidate_spec_lists, had_symbolic_dims): candidates are attempted
    in order by write_artifacts — first with dim 0 SHARED across all
    inputs (the batching contract; programs that relate their inputs'
    batch dims, e.g. x + y, only trace this way), then with fully
    independent symbols (inputs whose leading dims are genuinely
    unrelated)."""
    from ..static import InputSpec

    entries = []  # (shape_with_None, dtype)
    for s in input_spec:
        if isinstance(s, InputSpec):
            dims = [None if d is None or d < 0 else int(d) for d in s.shape]
            entries.append((dims, np.dtype(s.dtype)))
        elif isinstance(s, Tensor):
            entries.append((list(s._value.shape), np.dtype(s._value.dtype)))
        else:
            raise TypeError(f"bad input_spec entry {s!r}")
    n_none = sum(1 for dims, _ in entries for d in dims if d is None)
    symbolic = polymorphic and n_none > 0

    def build(share_dim0):
        names = {}  # (input_idx, dim_idx) -> symbol name
        for i, (dims, _) in enumerate(entries):
            for j, d in enumerate(dims):
                if d is None:
                    names[(i, j)] = ("b" if share_dim0 and j == 0
                                     else f"d{i}_{j}")
        syms = {}
        if symbolic and names:
            from jax import export as jax_export

            uniq = sorted(set(names.values()))
            sym_by_name = dict(zip(uniq,
                                   jax_export.symbolic_shape(
                                       ", ".join(uniq))))
            syms = {k: sym_by_name[v] for k, v in names.items()}
        specs = []
        for i, (dims, dt) in enumerate(entries):
            shape = tuple(syms[(i, j)] if symbolic and d is None
                          else (1 if d is None else d)
                          for j, d in enumerate(dims))
            specs.append(jax.ShapeDtypeStruct(shape, dt))
        return specs

    if not symbolic:
        return [build(False)], False
    candidates = [build(True)]
    if sum(1 for dims, _ in entries if dims and dims[0] is None) > 1:
        candidates.append(build(False))  # distinct only multi-input
    return candidates, True


def save(layer, path, input_spec=None, quant=None, quant_calib=None,
         mesh=None, **configs):
    """paddle.jit.save — export layer.forward at the given input spec.

    Dims given as None/-1 are exported batch-polymorphically (symbolic
    shapes), so the saved StableHLO can be run — and AOT-compiled per
    shape bucket by the serving engine — at any concrete size. A model
    that cannot trace under symbolic sizes fails the save (give it
    concrete dims instead); nothing is pinned to 1 behind its back.

    ``quant`` exports a QUANTIZED serving artifact (README "Quantized
    serving"): ``"w8"`` freezes every Linear/Conv2D to int8 weights +
    per-channel scales (in place, like ``quantization.quantize_weights``
    — the reference's slim/PTQ flow folded into the save); ``"w8a8"``
    additionally calibrates activation scales by running ``quant_calib``
    (a sample-batch generator) and bakes them in; ``"bf16w"`` stores
    f32 params as bf16 and upcasts inside the traced program (f32
    accumulate). The mode is recorded in ``.pdmeta.json`` and folded
    into the model fingerprint, so quantized programs are distinct
    artifact-store identities — they persist, single-flight, and
    cold-start-free across a replica fleet exactly like f32 ones.

    ``mesh`` records the SERVING MESH this save is intended for (a
    canonical descriptor — ``"tp2"``, ``"fsdp2xtp2"``; README "Sharded
    serving"). It does not change the exported program (sharding is a
    load-time layout of the runtime-arg weights, applied by the
    serving engines) — it is deployment intent, mirrored after the
    quant field: ``serve_model`` refuses to serve a save whose
    recorded mesh contradicts the declared one, at initial load AND on
    every hot reload."""
    if input_spec is None:
        raise ValueError("jit.save requires input_spec (list of InputSpec or Tensors)")
    if mesh is not None:
        from ..inference.sharding import ServingMesh

        # validate + canonicalize at save time: a typo'd descriptor
        # must fail the save, not every later load
        mesh = ServingMesh.parse(mesh).descriptor
    from ..quantization.serving import quantize_for_serving

    layer, quant_meta = quantize_for_serving(layer, quant,
                                             calib=quant_calib)
    # the RESOLVED mode: an already-in-place-quantized model (e.g. a
    # prior quant save of the same object, or PTQ's save flow) is
    # detected and recorded as what it IS — never stamped f32
    quant = quant_meta["mode"] if quant_meta else None
    spec_candidates, polymorphic = _build_input_specs(input_spec,
                                                      polymorphic=True)
    specs = spec_candidates[0]

    layer.eval()
    params, buffers = layer.functional_state()
    if quant == "bf16w":
        # the stored/streamed weights are bf16 (half the bytes the
        # decode hot path reads per token); the traced fn upcasts to
        # f32 below, so compute accumulates in f32 and the exported
        # program carries the convert ops
        # tests/test_quant_serving.py asserts on
        params = {n: a.astype(jnp.bfloat16)
                  if np.dtype(a.dtype) == np.dtype(np.float32) else a
                  for n, a in params.items()}
    param_names = list(params)
    buffer_names = list(buffers)

    fwd = layer.forward
    if isinstance(fwd, StaticFunction):
        fwd = fwd._orig_fn

    meta = {}

    def infer_fn(param_list, buffer_list, *inputs):
        saved_p = {n: p._value for n, p in layer.named_parameters()}
        saved_b = dict(zip(buffer_names, [buffers[n] for n in buffer_names]))
        if quant == "bf16w":
            # dequantize-into-compute: runtime args stay bf16, the
            # program converts once and accumulates in f32
            param_list = [p.astype(jnp.float32)
                          if p.dtype == jnp.bfloat16 else p
                          for p in param_list]
        try:
            with dispatch.trace_mode():
                layer.load_functional_state(dict(zip(param_names, param_list)),
                                            dict(zip(buffer_names, buffer_list)))
                out = fwd(*[Tensor(i, stop_gradient=True) for i in inputs])
                out_tensors, skel, _ = _flatten_tensors(out)
                meta["n_out"] = len(out_tensors)
                return tuple(t._value for t in out_tensors)
        finally:
            layer.load_functional_state(saved_p, saved_b)

    jitted = jax.jit(infer_fn)
    param_specs = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params.values()]
    buffer_specs = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in buffers.values()]

    write_artifacts(path, jitted, (param_specs, buffer_specs), specs,
                    {n: np.asarray(a) for n, a in params.items()},
                    {n: np.asarray(a) for n, a in buffers.items()},
                    spec_candidates=spec_candidates,
                    quant=quant, quant_meta=quant_meta, mesh=mesh)


def _is_symbolic_dim(d):
    return not isinstance(d, (int, np.integer))


def _json_spec(s):
    """JSON-safe (shape, dtype): symbolic dims serialize as None."""
    return ([None if _is_symbolic_dim(d) else int(d) for d in s.shape],
            str(s.dtype))


def write_artifacts(path, jitted_fn, state_specs, input_specs, params,
                    buffers, spec_candidates=None, quant=None,
                    quant_meta=None, mesh=None):
    """Serialize the single on-disk model format (<prefix>.pdmodel StableHLO +
    .pdiparams npz + .pdmeta.json sidecar) shared by jit.save and
    static.save_inference_model. ``jitted_fn(params_like, buffers_like,
    *inputs)``; state_specs = (param_specs, buffer_specs).

    Input specs may carry jax.export symbolic dims (batch-polymorphic
    save); ``spec_candidates`` orders alternative symbolic spellings of
    the same spec (shared batch dim first, then independent symbols).
    If no spelling exports, the save raises with the last error as its
    cause and writes no artifact."""
    from jax import export as jax_export

    from ..framework import op_version

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    exported = specs = err = None
    for cand in (spec_candidates or [input_specs]):
        try:
            exported = jax_export.export(jitted_fn)(*state_specs, *cand)
            specs = cand
            break
        except Exception as e:  # noqa: BLE001 - re-raised below
            err = e
    if exported is None:
        raise RuntimeError(
            f"jit.save({path!r}): the model does not export at input "
            f"spec {[_json_spec(s) for s in input_specs]} "
            f"({type(err).__name__}: {err}); give the failing dims "
            "concrete sizes or make the forward shape-polymorphic") from err
    blob = serialize_exported(exported)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    # .pdiparams is an npz (never pickle: loaded models may come from
    # untrusted sources, and np.load defaults to allow_pickle=False);
    # bfloat16 arrays round-trip as uint16 views since numpy's npz
    # format has no native bf16
    arrays = {}
    for prefix, d in (("p", params), ("b", buffers)):
        for n, a in d.items():
            a = np.asarray(a)
            if a.dtype.name == "bfloat16":
                arrays[f"{prefix}:bf16:{n}"] = a.view(np.uint16)
            else:
                arrays[f"{prefix}:raw:{n}"] = a
    import io as _io

    buf = _io.BytesIO()
    np.savez(buf, **arrays)
    with open(path + ".pdiparams", "wb") as f:
        f.write(buf.getvalue())
    with open(path + ".pdmeta.json", "w") as f:
        json.dump({"format": "stablehlo",
                   # the shapes actually exported (symbolic dims
                   # serialize as None)
                   "input_specs": [_json_spec(s) for s in specs],
                   "polymorphic": any(_is_symbolic_dim(d)
                                      for s in specs for d in s.shape),
                   # content identity of the exported program (weights
                   # are runtime args): the serving engine keys its
                   # persistent compiled-artifact store on this. The
                   # quant mode folds in, so quantized programs are
                   # distinct store identities.
                   "fingerprint": model_fingerprint(blob, quant=quant),
                   "op_versions": op_version.all_op_versions(),
                   # serving quant mode (None = f32) + its scale
                   # metadata: jit.load re-folds the mode into the
                   # fingerprint it computes from the module bytes
                   "quant": quant,
                   "quant_meta": quant_meta,
                   # intended serving mesh (None = unconstrained):
                   # serve_model fail-fasts on contradiction; the
                   # program itself is mesh-independent (weights are
                   # runtime args, sharded at load by the engines)
                   "mesh": mesh}, f)


class TranslatedLayer(Layer):
    """Loaded inference layer (reference: dygraph/io.py TranslatedLayer)."""

    def __init__(self, call_fn, params, buffers, input_specs=None,
                 polymorphic=False, fingerprint=None, quant=None,
                 mesh=None):
        super().__init__()
        self._call_fn = call_fn
        self._loaded_params = params
        self._loaded_buffers = buffers
        self._input_specs = input_specs or []
        # True when the saved module has symbolic (None) dims: it can be
        # called — and AOT-compiled per shape bucket — at any size there
        self._polymorphic = bool(polymorphic)
        # sha256 of the serialized module bytes (serialize.export): the
        # identity the serving engine's artifact store keys on; None
        # disables the store for engines over this layer
        self._model_fingerprint = fingerprint
        # serving quant mode the model was exported under (None = f32):
        # threaded into engine ArtifactKeys, compile metrics, and
        # ledger events so a mixed-precision fleet is observable
        self._quant_mode = quant
        # intended serving mesh recorded by jit.save(mesh=...) (None =
        # unconstrained): serve_model refuses a contradicting declared
        # mesh at load and on hot reload
        self._serving_mesh = mesh
        for i, (n, a) in enumerate(params.items()):
            from ..core.tensor import Parameter

            self.add_parameter(f"p_{i}", Parameter(jnp.asarray(a), name=n))

    def to_device(self, device):
        """Commit weights/buffers to `device` (a jax.Device) once, so run()
        never re-transfers them (Predictor device placement)."""
        for p in self._parameters.values():
            p._value = jax.device_put(p._value, device)
        self._loaded_buffers = {n: jax.device_put(jnp.asarray(b), device)
                                for n, b in self._loaded_buffers.items()}

    def forward(self, *inputs):
        param_list = [p._value for p in self._parameters.values()]
        buffer_list = [jnp.asarray(b) for b in self._loaded_buffers.values()]
        arrays = [i._value if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        out = self._call_fn(param_list, buffer_list, *arrays)
        outs = tuple(Tensor(o) for o in out)
        return outs[0] if len(outs) == 1 else outs


def _split_arrays(npz):
    params, buffers = {}, {}
    for key in npz.files:
        prefix, enc, name = key.split(":", 2)
        arr = npz[key]
        if enc == "bf16":
            import ml_dtypes

            arr = arr.view(ml_dtypes.bfloat16)
        (params if prefix == "p" else buffers)[name] = arr
    return params, buffers


def load(path, **configs):
    """paddle.jit.load — rebuild a callable Layer from the exported module."""
    with open(path + ".pdmeta.json") as f:
        payload = json.load(f)
    # allow_pickle stays False (default): params may be untrusted input
    with np.load(path + ".pdiparams") as npz:
        params, buffers = _split_arrays(npz)
    from ..framework import op_version

    op_version.check_compat(payload.get("op_versions"), where=path)
    if payload.get("format") == "stablehlo" and os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            blob = f.read()
        exported = deserialize_exported(blob)

        def call_fn(param_list, buffer_list, *inputs):
            return exported.call(param_list, buffer_list, *inputs)

        # computed from the bytes (not trusted from the sidecar): old
        # saves without a recorded fingerprint still key the artifact
        # store correctly. The quant mode re-folds into the hash, so a
        # quantized load carries the same distinct identity its save
        # recorded.
        quant = payload.get("quant")
        return TranslatedLayer(call_fn, params, buffers,
                               input_specs=payload.get("input_specs", []),
                               polymorphic=payload.get("polymorphic", False),
                               fingerprint=model_fingerprint(blob,
                                                             quant=quant),
                               quant=quant,
                               mesh=payload.get("mesh"))
    raise RuntimeError(
        f"model at {path} has no serialized program (format "
        f"{payload.get('format')!r}); re-save it with jit.save")
