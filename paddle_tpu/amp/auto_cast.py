"""auto_cast context (reference: python/paddle/amp/auto_cast.py,
imperative/amp_auto_cast.cc AmpOperators white/black lists)."""
import contextlib
import contextvars

import numpy as np
import jax.numpy as jnp

# bf16/fp16-safe ops (MXU-bound) — cast inputs down.
AMP_WHITE_LIST = {
    "matmul", "bmm", "mm", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "conv1d_transpose", "einsum", "fused_lstm", "fused_gru",
    "fused_rnn", "sdpa", "flash_attention", "short_attention", "addmm",
    # the expert gemms of the dropless MoE path, and the fused LM head +
    # loss (its logits and softmax are float32 inside)
    "moe_experts_sorted", "moe_experts_held", "linear_cross_entropy",
}

# numerically-sensitive ops — force fp32.
AMP_BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "pow", "square", "sqrt", "rsqrt",
    "softmax", "log_softmax", "cross_entropy", "nll_loss", "bce", "bce_logits",
    "kl_div", "mse_loss", "l1_loss", "smooth_l1_loss", "sum", "mean", "logsumexp",
    "cumsum", "layer_norm", "batch_norm_train", "batch_norm_infer", "group_norm",
    "instance_norm", "p_norm", "softmax_with_cross_entropy", "sigmoid_focal_loss",
}

white_list = AMP_WHITE_LIST
black_list = AMP_BLACK_LIST

_AMP_STATE = contextvars.ContextVar("amp_state", default=None)


class _AmpState:
    def __init__(self, enable, dtype, level, custom_white, custom_black):
        self.enable = enable
        self.dtype = jnp.bfloat16 if dtype in ("bfloat16", "bf16") else jnp.float16
        self.level = level
        self.white = (AMP_WHITE_LIST | set(custom_white or ())) - set(custom_black or ())
        self.black = (AMP_BLACK_LIST | set(custom_black or ())) - set(custom_white or ())


def _is_float_arr(v):
    try:
        d = np.dtype(v.dtype)
    except Exception:
        return False
    return d.kind == "f" or str(v.dtype) == "bfloat16"


def amp_cast_hook(name, arrays):
    """Called from core.dispatch.apply_op for every op."""
    state = _AMP_STATE.get()
    if state is None or not state.enable:
        return arrays
    if name in state.white:
        tgt = state.dtype
    elif name in state.black:
        tgt = jnp.float32
    elif state.level == "O2":
        tgt = state.dtype
    else:
        return arrays
    out = []
    for v in arrays:
        if v is not None and _is_float_arr(v) and v.dtype != tgt:
            out.append(v.astype(tgt))
        else:
            out.append(v)
    return out


def suspend_auto_cast():
    """Disable the per-op AMP hook for a region (the pipeline trunk
    uses explicit per-stage casts instead: per-op converts inside the
    manual shard_map region trip an XLA-CPU legalization CHECK).
    Exactly ``auto_cast(enable=False)`` — one hook-off protocol."""
    return auto_cast(enable=False)


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    state = _AmpState(enable, dtype, level, custom_white_list, custom_black_list)
    token = _AMP_STATE.set(state)
    try:
        yield
    finally:
        _AMP_STATE.reset(token)


amp_guard = auto_cast


def _install():
    from ..core import dispatch

    dispatch.AMP_HOOK = amp_cast_hook


_install()
