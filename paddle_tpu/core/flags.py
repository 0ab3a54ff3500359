"""Runtime flag system.

Analog of the reference's gflags registry + paddle.set_flags/get_flags
(reference: paddle/fluid/platform/flags.cc:33-461,
global_value_getter_setter.cc, python framework.py:6140). Flags are
initialised from ``FLAGS_*`` environment variables at import, like the
reference's init.cc env parsing.
"""
import os
import threading

_LOCK = threading.Lock()

# name -> (default, parser)
_REGISTRY = {}
_VALUES = {}


def _parse_bool(v):
    if isinstance(v, str):
        return v.lower() in ("1", "true", "yes", "on")
    return bool(v)


def define_flag(name, default, parser=None, help=""):
    with _LOCK:
        if name in _REGISTRY:
            return
        if parser is None:
            if isinstance(default, bool):
                parser = _parse_bool
            elif isinstance(default, int):
                parser = int
            elif isinstance(default, float):
                parser = float
            else:
                parser = str
        _REGISTRY[name] = (default, parser, help)
        env = os.environ.get("FLAGS_" + name)
        _VALUES[name] = parser(env) if env is not None else default


def set_flags(flags):
    """paddle.set_flags — dict of name -> value."""
    for name, value in flags.items():
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise KeyError(f"flag {name!r} is not registered")
        _VALUES[key] = _REGISTRY[key][1](value)


def flag_value(name):
    """Fast single-flag read for the hot dispatch path (no dict build,
    no FLAGS_ prefix handling — internal use)."""
    return _VALUES[name]


def get_flags(flags):
    """paddle.get_flags — name or list of names -> dict."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise KeyError(f"flag {name!r} is not registered")
        out[name] = _VALUES[key]
    return out


# Core flags (subset of the reference's 34 with TPU-meaningful semantics).
define_flag("check_nan_inf", False, help="scan every eager op output for NaN/Inf (flags.cc:44 analog; jax debug_nans for traced mode)")
define_flag("default_dtype", "float32", help="default floating dtype for creation ops")
define_flag("eager_jit_ops", True, help="dispatch eager ops through cached jax.jit for speed")
define_flag("benchmark", False, help="block_until_ready after each eager op for accurate timing")
define_flag("cudnn_deterministic", False, help="compat no-op; XLA is deterministic by default")
define_flag("use_pallas_kernels", True, help="use Pallas fused kernels (flash attention etc.) on TPU")
define_flag("pallas_interpret", False, help="run Pallas kernels in the Pallas interpreter (any backend) instead of compiling them with Mosaic, and select them off-TPU too. Set explicitly by the CPU tests and the chip_smoke dry run; never inferred from the backend")
define_flag("pallas_attention_min_seq", 1024, help="key length from which unmasked attention on [batch, heads, seq, head_dim] takes the STREAMING Pallas flash kernel (route 'stream' of ops/attention.py attention_route) instead of XLA's fused path. It gates only that route: short unmasked self-attention (seq <= 512) runs the whole-sequence kernel on the packed projection whatever this says. Measured on the v5e (2026-07-31): at seq 128 the streaming kernel's one-(batch, head)-per-program grid is 3x SLOWER than XLA's batched-matmul attention (one 128-block per program = pure per-program overhead); at seq 4096 it wins (XLA materialises S^2). 1024 = where the S^2 buffer starts to dominate activation memory. 0 = always the streaming kernel")
define_flag("allocator_strategy", "auto_growth", help="compat: XLA owns HBM allocation")
define_flag("fraction_of_gpu_memory_to_use", 0.92, help="compat no-op on TPU")
define_flag("seed", 0, help="global RNG seed")
