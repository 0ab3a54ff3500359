"""ONNX export (reference: python/paddle/onnx/export.py — which shells out
to paddle2onnx; here the Layer's forward is traced to a jaxpr and the
jaxpr equations are lowered 1:n to ONNX ops, serialized via wire.py).

The export path is the eval-mode inference graph: call ``layer.eval()``
first (random primitives — train-mode dropout — are rejected). Supported
primitive coverage is what the model zoo lowers to: dense math,
matmul/conv/pooling, reductions, shape ops, gather-embedding, select,
casts, and transparent inlining of nested jit/custom_jvp calls.
"""
import numpy as np

from . import wire


def export(layer, path, input_spec=None, opset_version=12, **configs):
    """Export ``layer`` to ``path + '.onnx'`` (reference signature:
    python/paddle/onnx/export.py:20). Supported opsets: 11 and 12 — the
    emitted Clip/Pad/Slice forms need >=11, GreaterOrEqual/LessOrEqual
    need >=12, and the ReduceSum axes-as-attribute form needs <=12."""
    if input_spec is None:
        raise ValueError(
            "input_spec is required: pass a list of InputSpec / Tensor / "
            "ndarray examples describing forward()'s inputs")
    model_bytes = export_bytes(layer, input_spec, opset_version,
                               **configs)
    out_path = path if path.endswith(".onnx") else path + ".onnx"
    with open(out_path, "wb") as f:
        f.write(model_bytes)
    return out_path


def export_bytes(layer, input_spec, opset_version=12, **configs):
    import jax

    if opset_version not in (11, 12):
        raise ValueError(
            f"opset_version {opset_version} unsupported: this exporter "
            f"emits opset 11/12 op forms (Clip/Pad/Slice inputs >=11, "
            f"ReduceSum axes-attribute <=12)")
    arrs = _example_arrays(input_spec)
    closed, param_names, param_vals = _trace(layer, [a for _, a in arrs])
    jaxpr = closed.jaxpr

    cv = _Converter(opset_version)
    # params + trace-closure constants (eval-mode buffers) → initializers
    n_params = len(param_names)
    for var, pname, val in zip(jaxpr.invars[:n_params], param_names,
                               param_vals):
        cv.bind(var, cv.add_init(np.asarray(val), pname))
    for var, (iname, arr) in zip(jaxpr.invars[n_params:], arrs):
        cv.bind(var, iname)
    for var, const in zip(jaxpr.constvars, closed.consts):
        cv.bind(var, cv.add_init(np.asarray(const)))

    cv.convert(jaxpr.eqns)

    inputs = [(iname, wire.onnx_dtype(arr.dtype), list(arr.shape))
              for iname, arr in arrs]
    outputs = []
    for i, var in enumerate(jaxpr.outvars):
        oname = f"output_{i}"
        cv.add_node("Identity", [cv.name_of(var)], [oname])
        outputs.append((oname, wire.onnx_dtype(var.aval.dtype),
                        list(var.aval.shape)))

    graph = wire.graph_proto("paddle_tpu_graph", cv.nodes, cv.initializers,
                             inputs, outputs)
    return wire.model_proto(graph, opset_version)


def _example_arrays(input_spec):
    from ..core.tensor import Tensor
    from ..static.input_spec import InputSpec

    arrs = []
    for i, spec in enumerate(input_spec):
        if isinstance(spec, InputSpec):
            if any(d is None or int(d) < 0 for d in spec.shape):
                raise ValueError(
                    f"input_spec[{i}] has dynamic dims {spec.shape}: ONNX "
                    "export traces a static-shape graph (XLA semantics); "
                    "export one model per concrete shape instead")
            shape = [int(d) for d in spec.shape]
            arrs.append((spec.name or f"x{i}",
                         np.zeros(shape, np.dtype(spec.dtype))))
        elif isinstance(spec, Tensor):
            arrs.append((spec.name or f"x{i}", np.asarray(spec._value)))
        else:
            arrs.append((f"x{i}", np.asarray(spec)))
    return arrs


def _trace(layer, xs):
    import jax

    from ..core import dispatch
    from ..core.tensor import Tensor

    params, _buffers = layer.functional_state()
    names = list(params)

    def fwd(plist, *inp):
        saved = {n: p._value for n, p in layer.named_parameters()}
        try:
            with dispatch.trace_mode():
                layer.load_functional_state(dict(zip(names, plist)))
                out = layer(*[Tensor(x, stop_gradient=True) for x in inp])
        finally:
            layer.load_functional_state(saved)
        outs = out if isinstance(out, (tuple, list)) else [out]
        return [o._value if isinstance(o, Tensor) else o for o in outs]

    closed = jax.make_jaxpr(fwd)([params[n] for n in names], *xs)
    return closed, names, [params[n] for n in names]


class UnsupportedOp(NotImplementedError):
    pass


class _Converter:
    def __init__(self, opset=12):
        self.opset = opset
        self.nodes = []            # serialized NodeProto bytes, in order
        self.initializers = {}     # name -> ndarray
        self._names = {}           # jaxpr Var -> onnx value name
        self._n = 0

    # -------------------------------------------------------- name plumbing
    def fresh(self, hint="v"):
        self._n += 1
        return f"{hint}_{self._n}"

    def bind(self, var, name):
        self._names[var] = name

    def name_of(self, var):
        if hasattr(var, "val"):  # jax Literal
            return self.add_init(np.asarray(var.val, dtype=var.aval.dtype))
        return self._names[var]

    def add_init(self, arr, name=None):
        name = name or self.fresh("const")
        self.initializers[name] = arr
        return name

    def i64(self, values):
        return self.add_init(np.asarray(values, dtype=np.int64))

    def add_node(self, op_type, inputs, outputs=None, attrs=None):
        outputs = outputs or [self.fresh(op_type.lower())]
        self.nodes.append(
            wire.node_proto(op_type, inputs, outputs,
                            name=self.fresh(op_type), attrs=attrs))
        return outputs

    # ------------------------------------------------------------- dispatch
    def convert(self, eqns):
        for eqn in eqns:
            prim = eqn.primitive.name
            if prim in _INLINE:
                sub, consts = _subjaxpr(eqn)
                for var, c in zip(sub.constvars, consts):
                    self.bind(var, self.add_init(np.asarray(c)))
                for inner, outer in zip(sub.invars, eqn.invars):
                    self.bind(inner, self.name_of(outer))
                self.convert(sub.eqns)
                for outer, inner in zip(eqn.outvars, sub.outvars):
                    self.bind(outer, self.name_of(inner))
                continue
            handler = _HANDLERS.get(prim)
            if handler is None:
                raise UnsupportedOp(
                    f"jax primitive '{prim}' has no ONNX lowering (shape "
                    f"{[v.aval.shape for v in eqn.invars]}); export supports "
                    f"eval-mode inference graphs only")
            handler(self, eqn)

    def out(self, eqn, name):
        self.bind(eqn.outvars[0], name)


_INLINE = {"jit", "pjit", "closed_call", "core_call", "xla_call",
           "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
           "custom_vjp_call_jaxpr", "remat", "checkpoint", "remat2",
           "custom_transpose_call", "name"}


def _subjaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            j = eqn.params[key]
            if hasattr(j, "jaxpr"):  # ClosedJaxpr
                return j.jaxpr, list(j.consts)
            return j, []
    raise UnsupportedOp(f"cannot find sub-jaxpr of '{eqn.primitive.name}'")


# ------------------------------------------------------------------ helpers

def _simple(op_type):
    def h(cv, eqn):
        outs = cv.add_node(op_type, [cv.name_of(v) for v in eqn.invars])
        cv.out(eqn, outs[0])
    return h


def _reduce(op_type):
    def h(cv, eqn):
        axes = [int(a) for a in eqn.params["axes"]]
        outs = cv.add_node(op_type, [cv.name_of(eqn.invars[0])],
                           attrs={"axes": axes, "keepdims": 0})
        cv.out(eqn, outs[0])
    return h


def _h_dot_general(cv, eqn):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars
    lshape, rshape = list(lhs.aval.shape), list(rhs.aval.shape)
    lfree = [d for d in range(len(lshape)) if d not in lc and d not in lb]
    rfree = [d for d in range(len(rshape)) if d not in rc and d not in rb]

    def _prep(var, shape, batch, free, contract, contract_first):
        """Transpose to [batch..., free/contract...] then flatten to 3-D."""
        order = (list(batch) + (list(contract) + list(free) if contract_first
                                else list(free) + list(contract)))
        name = cv.name_of(var)
        if order != list(range(len(shape))):
            name = cv.add_node("Transpose", [name],
                               attrs={"perm": order})[0]
        b = int(np.prod([shape[d] for d in batch])) if batch else 1
        f = int(np.prod([shape[d] for d in free])) if free else 1
        c = int(np.prod([shape[d] for d in contract])) if contract else 1
        dims3 = [b, c, f] if contract_first else [b, f, c]
        name = cv.add_node("Reshape", [name, cv.i64(dims3)])[0]
        return name

    lname = _prep(lhs, lshape, lb, lfree, lc, contract_first=False)
    rname = _prep(rhs, rshape, rb, rfree, rc, contract_first=True)
    mm = cv.add_node("MatMul", [lname, rname])[0]
    out_shape = list(eqn.outvars[0].aval.shape)
    final = cv.add_node("Reshape", [mm, cv.i64(out_shape)])[0]
    cv.out(eqn, final)


def _h_conv(cv, eqn):
    p = eqn.params
    dn = p["dimension_numbers"]
    spec = (dn.lhs_spec, dn.rhs_spec, dn.out_spec) if hasattr(dn, "lhs_spec") \
        else dn
    ndim = len(eqn.invars[0].aval.shape)
    nchw = tuple(range(ndim))
    oihw = tuple(range(ndim))
    if tuple(spec[0]) != nchw or tuple(spec[1]) != oihw or \
            tuple(spec[2]) != nchw:
        raise UnsupportedOp(f"conv layout {spec} (only NCHW/OIHW supported)")
    if any(d != 1 for d in p["lhs_dilation"]):
        raise UnsupportedOp("transposed conv (lhs_dilation>1)")
    pads_lo = [int(lo) for lo, _ in p["padding"]]
    pads_hi = [int(hi) for _, hi in p["padding"]]
    attrs = {
        "strides": [int(s) for s in p["window_strides"]],
        "pads": pads_lo + pads_hi,
        "dilations": [int(d) for d in p["rhs_dilation"]],
        "group": int(p["feature_group_count"]),
    }
    outs = cv.add_node("Conv", [cv.name_of(v) for v in eqn.invars],
                       attrs=attrs)
    cv.out(eqn, outs[0])


def _pool_attrs(eqn):
    p = eqn.params
    wd = [int(w) for w in p["window_dimensions"]]
    ws = [int(s) for s in p["window_strides"]]
    pad = [tuple(int(x) for x in pr) for pr in p["padding"]]
    if wd[:2] != [1, 1] or ws[:2] != [1, 1] or pad[0] != (0, 0) or \
            pad[1] != (0, 0):
        raise UnsupportedOp(f"reduce_window over non-spatial dims {wd}")
    if any(int(d) != 1 for d in p.get("base_dilation", [1] * len(wd))) or \
            any(int(d) != 1 for d in p.get("window_dilation", [1] * len(wd))):
        raise UnsupportedOp("dilated pooling")
    return {"kernel_shape": wd[2:], "strides": ws[2:],
            "pads": [pr[0] for pr in pad[2:]] + [pr[1] for pr in pad[2:]]}


def _h_maxpool(cv, eqn):
    outs = cv.add_node("MaxPool", [cv.name_of(eqn.invars[0])],
                       attrs=_pool_attrs(eqn))
    cv.out(eqn, outs[0])


def _h_sumpool(cv, eqn):
    attrs = _pool_attrs(eqn)
    count = int(np.prod(attrs["kernel_shape"]))
    attrs["count_include_pad"] = 1
    avg = cv.add_node("AveragePool", [cv.name_of(eqn.invars[0])],
                      attrs=attrs)[0]
    scale = cv.add_init(np.asarray(count, dtype=eqn.outvars[0].aval.dtype))
    outs = cv.add_node("Mul", [avg, scale])
    cv.out(eqn, outs[0])


def _h_broadcast_in_dim(cv, eqn):
    shape = [int(s) for s in eqn.params["shape"]]
    bdims = [int(d) for d in eqn.params["broadcast_dimensions"]]
    mid = [1] * len(shape)
    for src, dst in enumerate(bdims):
        mid[dst] = eqn.invars[0].aval.shape[src]
    name = cv.name_of(eqn.invars[0])
    if list(eqn.invars[0].aval.shape) != mid:
        name = cv.add_node("Reshape", [name, cv.i64(mid)])[0]
    if mid != shape:
        name = cv.add_node("Expand", [name, cv.i64(shape)])[0]
    elif name == cv.name_of(eqn.invars[0]):
        name = cv.add_node("Identity", [name])[0]
    cv.out(eqn, name)


def _h_reshape(cv, eqn):
    if eqn.params.get("dimensions") is not None:
        raise UnsupportedOp("reshape with dimension permutation")
    shape = [int(s) for s in eqn.params["new_sizes"]]
    outs = cv.add_node("Reshape",
                       [cv.name_of(eqn.invars[0]), cv.i64(shape)])
    cv.out(eqn, outs[0])


def _h_squeeze(cv, eqn):
    shape = [int(s) for s in eqn.outvars[0].aval.shape]
    outs = cv.add_node("Reshape",
                       [cv.name_of(eqn.invars[0]), cv.i64(shape)])
    cv.out(eqn, outs[0])


def _h_transpose(cv, eqn):
    perm = [int(p) for p in eqn.params["permutation"]]
    outs = cv.add_node("Transpose", [cv.name_of(eqn.invars[0])],
                       attrs={"perm": perm})
    cv.out(eqn, outs[0])


def _h_concatenate(cv, eqn):
    outs = cv.add_node("Concat", [cv.name_of(v) for v in eqn.invars],
                       attrs={"axis": int(eqn.params["dimension"])})
    cv.out(eqn, outs[0])


def _h_slice(cv, eqn):
    starts = [int(s) for s in eqn.params["start_indices"]]
    ends = [int(e) for e in eqn.params["limit_indices"]]
    strides = eqn.params.get("strides")
    steps = [int(s) for s in strides] if strides is not None \
        else [1] * len(starts)
    axes = list(range(len(starts)))
    outs = cv.add_node("Slice", [cv.name_of(eqn.invars[0]), cv.i64(starts),
                                 cv.i64(ends), cv.i64(axes), cv.i64(steps)])
    cv.out(eqn, outs[0])


def _h_pad(cv, eqn):
    cfg = [tuple(int(x) for x in c) for c in eqn.params["padding_config"]]
    if any(interior != 0 for _, _, interior in cfg):
        raise UnsupportedOp("interior padding")
    if any(lo < 0 or hi < 0 for lo, hi, _ in cfg):
        raise UnsupportedOp("negative padding")
    pads = [lo for lo, _, _ in cfg] + [hi for _, hi, _ in cfg]
    outs = cv.add_node("Pad", [cv.name_of(eqn.invars[0]), cv.i64(pads),
                               cv.name_of(eqn.invars[1])])
    cv.out(eqn, outs[0])


def _h_convert(cv, eqn):
    to = wire.onnx_dtype(np.dtype(eqn.params["new_dtype"]).name)
    outs = cv.add_node("Cast", [cv.name_of(eqn.invars[0])],
                       attrs={"to": to})
    cv.out(eqn, outs[0])


def _h_select_n(cv, eqn):
    if len(eqn.invars) != 3:
        raise UnsupportedOp(f"select_n with {len(eqn.invars) - 1} cases")
    pred, case0, case1 = eqn.invars
    # select_n picks cases[int(pred)]: pred False -> case0, True -> case1;
    # ONNX Where(cond, X, Y) yields X where cond is True.
    outs = cv.add_node("Where", [cv.name_of(pred), cv.name_of(case1),
                                 cv.name_of(case0)])
    cv.out(eqn, outs[0])


def _h_gather(cv, eqn):
    dn = eqn.params["dimension_numbers"]
    operand, indices = eqn.invars
    oshape = list(operand.aval.shape)
    slice_sizes = [int(s) for s in eqn.params["slice_sizes"]]
    ishape = list(indices.aval.shape)
    embedding_like = (
        tuple(dn.start_index_map) == (0,)
        and tuple(dn.collapsed_slice_dims) == (0,)
        and slice_sizes == [1] + oshape[1:]
        and ishape and ishape[-1] == 1
        and not getattr(dn, "operand_batching_dims", ())
    )
    if not embedding_like:
        raise UnsupportedOp(
            f"general gather {dn} (only axis-0 embedding lookup supported)")
    idx = cv.add_node("Reshape",
                      [cv.name_of(indices), cv.i64(ishape[:-1])])[0]
    gathered = cv.add_node("Gather", [cv.name_of(operand), idx],
                           attrs={"axis": 0})[0]
    out_shape = list(eqn.outvars[0].aval.shape)
    final = cv.add_node("Reshape", [gathered, cv.i64(out_shape)])[0]
    cv.out(eqn, final)


def _h_iota(cv, eqn):
    shape = [int(s) for s in eqn.params["shape"]]
    dim = int(eqn.params["dimension"])
    dtype = np.dtype(eqn.params["dtype"])
    rng = np.arange(shape[dim], dtype=dtype)
    view = [1] * len(shape)
    view[dim] = shape[dim]
    arr = np.broadcast_to(rng.reshape(view), shape).copy()
    cv.out(eqn, cv.add_init(arr))


def _h_rsqrt(cv, eqn):
    s = cv.add_node("Sqrt", [cv.name_of(eqn.invars[0])])[0]
    outs = cv.add_node("Reciprocal", [s])
    cv.out(eqn, outs[0])


def _h_square(cv, eqn):
    x = cv.name_of(eqn.invars[0])
    outs = cv.add_node("Mul", [x, x])
    cv.out(eqn, outs[0])


def _h_erfc(cv, eqn):
    e = cv.add_node("Erf", [cv.name_of(eqn.invars[0])])[0]
    one = cv.add_init(np.asarray(1.0, dtype=eqn.outvars[0].aval.dtype))
    outs = cv.add_node("Sub", [one, e])
    cv.out(eqn, outs[0])


def _h_integer_pow(cv, eqn):
    y = cv.add_init(np.asarray(eqn.params["y"],
                               dtype=eqn.invars[0].aval.dtype))
    outs = cv.add_node("Pow", [cv.name_of(eqn.invars[0]), y])
    cv.out(eqn, outs[0])


def _h_clamp(cv, eqn):
    lo, x, hi = eqn.invars
    outs = cv.add_node("Clip", [cv.name_of(x), cv.name_of(lo),
                                cv.name_of(hi)])
    cv.out(eqn, outs[0])


def _h_argminmax(op_type):
    def h(cv, eqn):
        axes = eqn.params["axes"]
        res = cv.add_node(op_type, [cv.name_of(eqn.invars[0])],
                          attrs={"axis": int(axes[0]), "keepdims": 0})[0]
        want = np.dtype(eqn.params["index_dtype"])
        if want != np.int64:
            res = cv.add_node("Cast", [res],
                              attrs={"to": wire.onnx_dtype(want.name)})[0]
        cv.out(eqn, res)
    return h


def _h_opset12(op_type):
    def h(cv, eqn):
        if cv.opset < 12:
            raise UnsupportedOp(
                f"{op_type} requires opset >= 12 (export with "
                f"opset_version=12)")
        outs = cv.add_node(op_type, [cv.name_of(v) for v in eqn.invars])
        cv.out(eqn, outs[0])
    return h


def _h_rem(cv, eqn):
    # lax.rem is C-style truncated remainder (sign of dividend) = fmod;
    # ONNX Mod defaults to floored modulo and requires fmod=1 for floats
    outs = cv.add_node("Mod", [cv.name_of(v) for v in eqn.invars],
                       attrs={"fmod": 1})
    cv.out(eqn, outs[0])


def _h_ne(cv, eqn):
    eq = cv.add_node("Equal", [cv.name_of(v) for v in eqn.invars])[0]
    outs = cv.add_node("Not", [eq])
    cv.out(eqn, outs[0])


def _h_split(cv, eqn):
    sizes = [int(s) for s in eqn.params["sizes"]]
    axis = int(eqn.params["axis"])
    outs = cv.add_node("Split", [cv.name_of(eqn.invars[0])],
                       outputs=[cv.fresh("split") for _ in sizes],
                       attrs={"axis": axis, "split": sizes})
    for var, name in zip(eqn.outvars, outs):
        cv.bind(var, name)


def _h_rev(cv, eqn):
    dims = [int(d) for d in eqn.params["dimensions"]]
    shape = list(eqn.invars[0].aval.shape)
    starts = [shape[d] - 1 for d in dims]
    ends = [-shape[d] - 1 for d in dims]
    steps = [-1] * len(dims)
    outs = cv.add_node("Slice", [cv.name_of(eqn.invars[0]), cv.i64(starts),
                                 cv.i64(ends), cv.i64(dims), cv.i64(steps)])
    cv.out(eqn, outs[0])


_HANDLERS = {
    "add": _simple("Add"), "sub": _simple("Sub"), "mul": _simple("Mul"),
    "div": _simple("Div"), "max": _simple("Max"), "min": _simple("Min"),
    "pow": _simple("Pow"), "rem": _h_rem,
    "neg": _simple("Neg"), "exp": _simple("Exp"), "log": _simple("Log"),
    "tanh": _simple("Tanh"), "logistic": _simple("Sigmoid"),
    "sqrt": _simple("Sqrt"), "abs": _simple("Abs"), "sign": _simple("Sign"),
    "floor": _simple("Floor"), "ceil": _simple("Ceil"),
    "round": _simple("Round"), "erf": _simple("Erf"),
    "erfc": _h_erfc, "rsqrt": _h_rsqrt, "square": _h_square,
    "integer_pow": _h_integer_pow, "clamp": _h_clamp,
    "is_finite": None,  # replaced below to raise clearly
    "stop_gradient": _simple("Identity"), "copy": _simple("Identity"),
    # placement (device_put eqns inside the jaxpr) has no ONNX meaning
    "device_put": _simple("Identity"),
    "gt": _simple("Greater"), "lt": _simple("Less"),
    "ge": _h_opset12("GreaterOrEqual"), "le": _h_opset12("LessOrEqual"),
    "eq": _simple("Equal"), "ne": _h_ne,
    "and": _simple("And"), "or": _simple("Or"), "not": _simple("Not"),
    "xor": _simple("Xor"),
    "reduce_sum": _reduce("ReduceSum"), "reduce_max": _reduce("ReduceMax"),
    "reduce_min": _reduce("ReduceMin"),
    "reduce_prod": _reduce("ReduceProd"),
    "argmax": _h_argminmax("ArgMax"), "argmin": _h_argminmax("ArgMin"),
    "dot_general": _h_dot_general,
    "conv_general_dilated": _h_conv,
    "reduce_window_max": _h_maxpool,
    "reduce_window_sum": _h_sumpool,
    "broadcast_in_dim": _h_broadcast_in_dim,
    "reshape": _h_reshape, "squeeze": _h_squeeze,
    "transpose": _h_transpose, "concatenate": _h_concatenate,
    "slice": _h_slice, "pad": _h_pad, "split": _h_split,
    "convert_element_type": _h_convert,
    "select_n": _h_select_n, "gather": _h_gather, "iota": _h_iota,
    "rev": _h_rev,
}
del _HANDLERS["is_finite"]
