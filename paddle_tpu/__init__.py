"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/pjit/Pallas.

Usage mirrors paddle (``import paddle_tpu as paddle``): dygraph by
default, ``paddle.jit.to_static`` for compiled execution, ``paddle.static``
facade, ``paddle.distributed``/fleet for mesh parallelism.
"""

__version__ = "0.1.0"

# TPU dtype policy: compute stays 32-bit (x64 OFF — int64/float64 index and
# embedding traffic double HBM bandwidth and block Mosaic lowering). Paddle's
# int64/float64 API names remain accepted everywhere and canonicalize to the
# 32-bit equivalents via core.dtype.convert_dtype — the per-op dtype policy
# replacing the reference's VarType.INT64 default (framework.proto:23-60).
from .core import dispatch as _dispatch
from .core import dtype as _dtype
from .core import errors, flags as _flags
from .core import place as _place
from .core import random as _random
from .core import tape as _tape
from .core.tensor import Tensor, to_tensor  # noqa: F401

# jax is imported: its trace / lower / compile events become spans, and so
# do the interpreter's collections
from .obs import ledger as _ledger

_ledger.bridge_jax_monitoring()
_ledger.bridge_gc()

# dtypes
from .core.dtype import (  # noqa: F401
    bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, get_default_dtype, set_default_dtype,
)

# places / device
from .core.place import (  # noqa: F401
    CPUPlace, CUDAPlace, CUDAPinnedPlace, TPUPlace, XPUPlace, NPUPlace,
    set_device, get_device, is_compiled_with_cuda, is_compiled_with_xpu,
    is_compiled_with_tpu, device_count,
)

# flags
from .core.flags import set_flags, get_flags  # noqa: F401

# rng
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401

# autograd context
no_grad = _dispatch.no_grad_ctx
enable_grad = _dispatch.enable_grad_ctx
grad = _tape.grad

# full tensor-op namespace (paddle.add, paddle.matmul, ...)
from .tensor import *  # noqa: F401,F403
from .tensor import einsum  # noqa: F401
from . import tensor  # noqa: F401

from . import autograd  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import metric  # noqa: F401
from . import io  # noqa: F401
from . import vision  # noqa: F401
from . import amp  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import distributed  # noqa: F401
from . import distribution  # noqa: F401
from . import hapi  # noqa: F401
from . import text  # noqa: F401
from . import dataset  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import quantization  # noqa: F401
from . import onnx  # noqa: F401
from . import utils  # noqa: F401
from . import analysis  # noqa: F401 (tracelint: trace-safety static analyzer)
from . import resilience  # noqa: F401 (fault-tolerant training runtime)
from . import serialize  # noqa: F401 (program export + artifact store)
from .hapi import Model, summary  # noqa: F401
from .framework import save, load  # noqa: F401
from . import framework  # noqa: F401
from .nn.layer import Layer  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .jit import disable_static, enable_static, in_dynamic_mode  # noqa: F401


def ones_like(x, dtype=None, name=None):  # ensure top-level symbol  # noqa: F811
    from .tensor import creation

    return creation.ones_like(x, dtype, name)


def is_grad_enabled():
    return _dispatch.tape_enabled()


def set_grad_enabled(mode):
    class _Ctx:
        def __enter__(self):
            self._tok = _dispatch._TAPE_ENABLED.set(bool(mode))

        def __exit__(self, *e):
            _dispatch._TAPE_ENABLED.reset(self._tok)

    return _Ctx()


# ------------------------------------------------- top-level API parity
# (reference: python/paddle/__init__.py exports)
from . import fluid  # noqa: F401 (1.x-era compat namespace)
from . import hub  # noqa: F401
from .core.tensor import Tensor as VarBase  # noqa: F401 (legacy alias)
from .framework.param_attr import ParamAttr  # noqa: F401
from .framework import in_dygraph_mode  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .hapi.flops import flops  # noqa: F401

import numpy as _np

dtype = _np.dtype  # paddle.dtype: the type of Tensor.dtype values


def enable_dygraph(place=None):
    """Legacy alias (reference: fluid/dygraph/base.py enable_dygraph)."""
    disable_static()


def disable_dygraph():
    enable_static()


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batch reader (reference:
    python/paddle/batch.py)."""

    def batch_reader():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batch_reader


def get_cudnn_version():
    return None  # not a CUDA build


def is_compiled_with_npu():
    return False


def get_cuda_rng_state():
    """Device RNG state (TPU analog of the CUDA generator state)."""
    return get_rng_state()


def set_cuda_rng_state(state_list):
    set_rng_state(state_list)
