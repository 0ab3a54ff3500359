"""Layer — module base class.

Reference: python/paddle/fluid/dygraph/layers.py:80 (Layer, __call__: 875,
hooks, state_dict) — rebuilt over the functional core. A Layer owns
Parameters (mutable-shell Tensors); the functional view needed by
jit/pjit (params-as-pytree) is provided by ``functional_state`` /
``load_functional_state``, which to_static and the distributed train
steps use to thread parameters through pure functions.
"""
import collections
import contextlib
import math

import jax
import numpy as np

from ..core import dispatch
from ..core import dtype as dtype_mod
from ..core.tensor import Parameter, Tensor
from ..framework.param_attr import ParamAttr
from ..obs import tracing
from . import initializer as init_mod

_LAYER_COUNTERS = collections.defaultdict(int)


class HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks = hooks
        self._key = key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        if name_scope is None:
            name_scope = type(self).__name__.lower()
        idx = _LAYER_COUNTERS[name_scope]
        _LAYER_COUNTERS[name_scope] += 1
        object.__setattr__(self, "_full_name", f"{name_scope}_{idx}")
        object.__setattr__(self, "_dtype", dtype)
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names_set", set())
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_forward_pre_hooks", collections.OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", collections.OrderedDict())
        object.__setattr__(self, "_hook_counter", 0)

    # ------------------------------------------------------------ naming
    def full_name(self):
        return self._full_name

    # ------------------------------------------------------------ params
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        np_dtype = np.dtype(dtype_mod.convert_dtype(dtype))
        init = attr.initializer or default_initializer or init_mod.global_initializer(is_bias)
        if init is None:
            init = init_mod.Constant(0.0) if is_bias else init_mod.XavierNormal()
        shape = tuple(int(s) for s in shape)
        # one ``nn.init`` span a parameter where values are really drawn
        # (and the draw's program compiled on a shape's first use): not
        # inside a program jax is tracing
        with (contextlib.nullcontext() if dispatch.in_trace() else
              tracing.span("nn.init", initializer=type(init).__name__,
                           bytes=math.prod(shape) * np_dtype.itemsize)):
            value = init._generate(shape, np_dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        if p.name is None:
            p.name = f"{self._full_name}.w_{len(self._parameters)}"
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            object.__setattr__(sublayer, "_scope_attr", str(name))
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        return tensor

    # ------------------------------------------------------------ attr magic
    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            self.__dict__.pop(name, None)
            if value.name is None:
                value.name = f"{self._full_name}.{name}"
            self._parameters[name] = value
        elif isinstance(value, Layer):
            self.__dict__.pop(name, None)
            self._sub_layers[name] = value
            object.__setattr__(value, "_scope_attr", name)
        elif isinstance(value, (list, tuple)) and value and all(
                isinstance(v, Parameter) for v in value):
            # ParameterList-like assignment
            object.__setattr__(self, name, value)
            for i, p in enumerate(value):
                self._parameters[f"{name}.{i}"] = p
        else:
            if name in getattr(self, "_parameters", {}):
                del self._parameters[name]
            if name in getattr(self, "_sub_layers", {}):
                del self._sub_layers[name]
            if name in getattr(self, "_buffers", {}):
                self._buffers[name] = value
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        if name in self._parameters:
            del self._parameters[name]
        elif name in self._sub_layers:
            del self._sub_layers[name]
        elif name in self._buffers:
            del self._buffers[name]
            self._non_persistable_buffer_names_set.discard(name)
        else:
            object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._sub_layers) + list(self._buffers)

    # ------------------------------------------------------------ iteration
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None:
                    continue
                yield (f"{name}.{bname}" if name else bname), b
            if not include_sublayers:
                break

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            subprefix = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=subprefix, include_self=True)

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ------------------------------------------------------------ modes
    def train(self):
        for layer in self.sublayers(include_self=True):
            object.__setattr__(layer, "training", True)
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            object.__setattr__(layer, "training", False)
        return self

    # ------------------------------------------------------------ state dict
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = collections.OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(prefix=structured_name_prefix,
                                             include_sublayers=include_sublayers):
            dest[name] = p
        for name, layer in self.named_sublayers(prefix=structured_name_prefix,
                                                include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names_set:
                    continue
                key = f"{name}.{bname}" if name else bname
                dest[key] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for key, value in state_dict.items():
            if key not in own:
                unexpected.append(key)
                continue
            tgt = own[key]
            arr = value.numpy() if hasattr(value, "numpy") else np.asarray(value)
            tgt.set_value(arr.astype(np.dtype(tgt.dtype)) if arr.dtype != np.dtype(tgt.dtype)
                          and np.dtype(tgt.dtype).name != "bfloat16" else arr)
        for key in own:
            if key not in state_dict:
                missing.append(key)
        return missing, unexpected

    load_dict = set_state_dict

    # ------------------------------------------------------------ functional view
    def functional_state(self):
        """(param_arrays, buffer_arrays) pytrees keyed by structured name —
        the bridge from mutable Layer to pure-function training steps.
        Covers Tensor buffers (BatchNorm stats) and raw-array buffers
        (QAT scales) alike."""
        params = {name: p._value for name, p in self.named_parameters()}
        buffers = {}
        for name, layer in self.named_sublayers(include_self=True):
            for bname, b in layer._buffers.items():
                key = f"{name}.{bname}" if name else bname
                if isinstance(b, Tensor):
                    buffers[key] = b._value
                elif isinstance(b, np.ndarray) or \
                        type(b).__module__.startswith("jax"):
                    buffers[key] = b
        return params, buffers

    def load_functional_state(self, params=None, buffers=None):
        if params:
            lookup = dict(self.named_parameters())
            for name, arr in params.items():
                if name in lookup:
                    lookup[name]._value = arr
        if buffers:
            blookup = {}
            for name, layer in self.named_sublayers(include_self=True):
                for bname in layer._buffers:
                    blookup[f"{name}.{bname}" if name else bname] = \
                        (layer, bname)
            for name, arr in buffers.items():
                if name in blookup:
                    layer, bname = blookup[name]
                    cur = layer._buffers[bname]
                    if isinstance(cur, Tensor):
                        cur._value = arr
                    else:
                        layer._buffers[bname] = arr

    # ------------------------------------------------------------ hooks
    def register_forward_pre_hook(self, hook):
        key = self._hook_counter
        object.__setattr__(self, "_hook_counter", key + 1)
        self._forward_pre_hooks[key] = hook
        return HookRemoveHelper(self._forward_pre_hooks, key)

    def register_forward_post_hook(self, hook):
        key = self._hook_counter
        object.__setattr__(self, "_hook_counter", key + 1)
        self._forward_post_hooks[key] = hook
        return HookRemoveHelper(self._forward_post_hooks, key)

    # ------------------------------------------------------------ call
    def scope_name(self):
        """The module's ``jax.named_scope`` in a traced program: its class
        and, where a parent registered it, the attribute it is held under
        (``self_attn:MultiHeadAttention``). A trace reader finds a
        module's operations by it in the HLO ``op_name``."""
        attr = self.__dict__.get("_scope_attr")
        cls = type(self).__name__
        return f"{attr}:{cls}" if attr else cls

    def __call__(self, *inputs, **kwargs):
        if dispatch.in_trace():
            # scopes are HLO metadata, written while tracing only: eager
            # dispatch enters nothing
            with jax.named_scope(self.scope_name()):
                return self._call(inputs, kwargs)
        return self._call(inputs, kwargs)

    def _call(self, inputs, kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------ conversion
    def to(self, device=None, dtype=None, blocking=None):
        import jax

        for t in list(self.parameters()) + list(self.buffers()):
            if dtype is not None and dtype_mod.is_floating(t.dtype):
                nd = dtype_mod.convert_dtype(dtype)
                t._value = t._value.astype(nd)
            if device is not None:
                from ..core import place as place_mod

                pl = place_mod.set_device(device) if isinstance(device, str) else device
                t._value = jax.device_put(t._value, pl.jax_device())
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def __repr__(self):
        extra = []
        for name, sub in self._sub_layers.items():
            rep = repr(sub).replace("\n", "\n  ")
            extra.append(f"  ({name}): {rep}")
        body = "\n".join(extra)
        cls = type(self).__name__
        return f"{cls}(\n{body}\n)" if body else f"{cls}()"
