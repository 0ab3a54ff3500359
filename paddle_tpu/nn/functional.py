"""nn functional ops (reference: python/paddle/nn/functional/*; kernels
operators/conv_op.cc, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc,
softmax_op.cc, cross_entropy_op.cc, dropout_op.cc, activation_op.cc).

Convolutions/matmuls map onto the MXU via lax.conv_general_dilated /
jnp.matmul; norms and activations are VPU element-wise code that XLA
fuses into neighbors. Data layout: paddle defaults to NCHW at the API,
but kernels transpose to NHWC internally when beneficial — XLA on TPU
canonicalises layout anyway, so we keep the math in the API layout.
"""
import functools as _pyfunctools
import math as _pymath

import numpy as np
import jax
import jax.numpy as jnp

from ..core import random as random_core
from ..core.dispatch import apply_op
from ..core.tensor import Tensor

# ------------------------------------------------------------- activations


def _unary(op_name, fn):
    def api(x, name=None):
        return apply_op(op_name, fn, x)

    api.__name__ = op_name
    return api


relu = _unary("relu", lambda x: jax.nn.relu(x))
relu6 = _unary("relu6", lambda x: jax.nn.relu6(x))
sigmoid = _unary("sigmoid", lambda x: jax.nn.sigmoid(x))
tanh = _unary("tanh", lambda x: jnp.tanh(x))
silu = _unary("silu", lambda x: jax.nn.silu(x))
swish = silu
def mish(x, threshold=20.0, name=None):
    """reference: fluid/layers/nn.py mish — softplus switches to the
    identity above ``threshold`` for numerical stability."""

    def _mish(x, *, threshold):
        sp = jnp.where(x > threshold, x, jax.nn.softplus(x))
        return x * jnp.tanh(sp)

    return apply_op("mish", _mish, x, threshold=float(threshold))
tanhshrink = _unary("tanhshrink", lambda x: x - jnp.tanh(x))
softsign = _unary("softsign", lambda x: jax.nn.soft_sign(x))
log_sigmoid = _unary("log_sigmoid", lambda x: jax.nn.log_sigmoid(x))


_SQRT_HALF = _pymath.sqrt(0.5)
_INV_SQRT_2PI = 1.0 / _pymath.sqrt(2.0 * _pymath.pi)


def _wide(x):
    """``x`` in the dtype gelu computes in: float32 for a half input."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


@jax.custom_jvp
def _erf_gelu(x):
    xw = _wide(x)
    return (0.5 * xw * (1.0 + jax.lax.erf(xw * _SQRT_HALF))).astype(x.dtype)


@_erf_gelu.defjvp
def _erf_gelu_jvp(primals, tangents):
    """d/dx = Phi(x) + x * phi(x), from ``x`` alone. A differentiated
    program keeps gelu's value behind a barrier: the next layer's gemm and
    its weight gradient both read it, and XLA otherwise drops it and
    evaluates the erf again in the operand of each (12 ms a step of
    BERT-base against the 0.2 GB a layer it saves, PERF.md PR 26)."""
    (x,), (t,) = primals, tangents
    xw = _wide(x)
    slope = (0.5 * (1.0 + jax.lax.erf(xw * _SQRT_HALF))
             + xw * jnp.exp(-0.5 * xw * xw) * _INV_SQRT_2PI)
    return (jax.lax.optimization_barrier(_erf_gelu(x)),
            (_wide(t) * slope).astype(x.dtype))


def _gelu(x, *, approx):
    if approx or not jnp.issubdtype(x.dtype, jnp.floating):
        return jax.nn.gelu(x, approximate=approx)
    return _erf_gelu(x)


def gelu(x, approximate=False, name=None):
    """reference: operators/gelu_op.h — the exact form is
    ``x * 0.5 * (1 + erf(x * M_SQRT1_2))``, computed in float32 for half
    inputs (float64 stays float64) and rounded once, to the input's dtype.

    Why erf and not ``jax.nn.gelu``'s ``0.5 * x * erfc(-x * sqrt(0.5))``:
    ``erf`` is one native op on the TPU, while XLA expands ``erfc`` into
    all three of its polynomial branches on every element (~90 vector
    operations against ~35), which made the FFN-up gemm's epilogue twice as
    long as the gemm. The erf form keeps 4e-7 * max(1, |x|) of absolute
    accuracy in float32 but gives up *relative* accuracy below x ~ -5,
    where 1 + erf cancels and |gelu| < 1e-6. ``approximate=True`` is
    ``jax.nn.gelu``'s tanh form."""
    return apply_op("gelu", _gelu, x, approx=bool(approximate))


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply_op("leaky_relu",
                    lambda x, *, slope: jax.nn.leaky_relu(x, negative_slope=slope),
                    x, slope=float(negative_slope))


def elu(x, alpha=1.0, name=None):
    return apply_op("elu", lambda x, *, alpha: jax.nn.elu(x, alpha=alpha), x, alpha=float(alpha))


def celu(x, alpha=1.0, name=None):
    return apply_op("celu", lambda x, *, alpha: jax.nn.celu(x, alpha=alpha), x, alpha=float(alpha))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply_op(
        "selu", lambda x, *, s, a: s * jnp.where(x > 0, x, a * jnp.expm1(x)),
        x, s=float(scale), a=float(alpha))


def hardshrink(x, threshold=0.5, name=None):
    return apply_op(
        "hardshrink", lambda x, *, t: jnp.where(jnp.abs(x) > t, x, 0.0), x, t=float(threshold))


def softshrink(x, threshold=0.5, name=None):
    return apply_op(
        "softshrink",
        lambda x, *, t: jnp.where(x > t, x - t, jnp.where(x < -t, x + t, 0.0)),
        x, t=float(threshold))


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply_op(
        "hardsigmoid", lambda x, *, s, o: jnp.clip(s * x + o, 0.0, 1.0),
        x, s=float(slope), o=float(offset))


def hardswish(x, name=None):
    return apply_op("hardswish", lambda x: x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0), x)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply_op("hardtanh", lambda x, *, lo, hi: jnp.clip(x, lo, hi),
                    x, lo=float(min), hi=float(max))


def prelu(x, weight, data_format="NCHW", name=None):
    def _prelu(x, w, *, data_format):
        if w.size == 1:
            return jnp.where(x >= 0, x, w.reshape(()) * x)
        shape = [1] * x.ndim
        ch = 1 if data_format.startswith("NC") else x.ndim - 1
        shape[ch] = w.size
        return jnp.where(x >= 0, x, w.reshape(shape) * x)

    return apply_op("prelu", _prelu, x, weight, data_format=data_format)


def softplus(x, beta=1, threshold=20, name=None):
    return apply_op(
        "softplus",
        lambda x, *, beta, threshold: jnp.where(
            beta * x > threshold, x, jnp.log1p(jnp.exp(beta * x)) / beta),
        x, beta=float(beta), threshold=float(threshold))


def maxout(x, groups, axis=1, name=None):
    def _maxout(x, *, groups, axis):
        ax = axis % x.ndim
        c = x.shape[ax]
        new_shape = x.shape[:ax] + (c // groups, groups) + x.shape[ax + 1:]
        return jnp.max(x.reshape(new_shape), axis=ax + 1)

    return apply_op("maxout", _maxout, x, groups=int(groups), axis=int(axis))


def softmax(x, axis=-1, dtype=None, name=None):
    return apply_op("softmax", lambda x, *, axis: jax.nn.softmax(x, axis=axis), x, axis=int(axis))


def log_softmax(x, axis=-1, dtype=None, name=None):
    return apply_op("log_softmax", lambda x, *, axis: jax.nn.log_softmax(x, axis=axis),
                    x, axis=int(axis))


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    def _gs(key, x, *, tau, hard, axis):
        g = jax.random.gumbel(key, x.shape, x.dtype)
        y = jax.nn.softmax((x + g) / tau, axis=axis)
        if hard:
            # straight-through: hard one-hot forward, soft gradient
            idx = jnp.argmax(y, axis=axis)
            oh = jax.nn.one_hot(idx, y.shape[axis], axis=axis, dtype=y.dtype)
            return oh + y - jax.lax.stop_gradient(y)
        return y

    return apply_op("gumbel_softmax", _gs, random_core.next_key(), x,
                    tau=float(temperature), hard=bool(hard), axis=int(axis))


# ------------------------------------------------------------- linear / embedding


def linear(x, weight, bias=None, name=None):
    """reference: operators/matmul_v2 + elementwise_add fusion (fc)."""

    def _linear(x, w, b):
        y = jnp.matmul(x, w)
        if b is not None:
            y = y + b
        return y

    return apply_op("linear", _linear, x, weight, bias)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """reference: operators/lookup_table_v2_op.cc. `sparse` is accepted for
    API compat; on TPU the gather is dense and XLA-sharded."""

    def _embedding(ids, w, *, padding_idx):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply_op("embedding", _embedding, x, weight,
                    padding_idx=None if padding_idx is None else int(padding_idx))


def one_hot(x, num_classes, name=None):
    return apply_op(
        "one_hot", lambda x, *, n: jax.nn.one_hot(x.astype(jnp.int32), n, dtype=jnp.float32),
        x, n=int(num_classes))


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def _ls(label, prior, *, eps):
        n = label.shape[-1]
        if prior is None:
            return (1 - eps) * label + eps / n
        return (1 - eps) * label + eps * prior

    return apply_op("label_smooth", _ls, label, prior_dist, eps=float(epsilon))


# ------------------------------------------------------------- dropout


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    """reference: operators/dropout_op.cc (upscale_in_train default;
    downscale_in_infer scales by (1-p) at inference)."""
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and p > 0.0:
            return apply_op("dropout_infer_downscale",
                            lambda x, *, keep: x * keep, x, keep=1.0 - float(p))
        return x if isinstance(x, Tensor) else Tensor(x)

    ax = tuple(np.atleast_1d(axis).tolist()) if axis is not None else None

    def _dropout(key, x, *, p, mode, axis):
        shape = x.shape
        if axis is not None:
            shape = tuple(s if i in axis else 1 for i, s in enumerate(x.shape))
        # counter-hash mask, not threefry bernoulli: dropout masks are the
        # single biggest RNG cost in a training step (core/random.py
        # fast_keep_mask for the v5e measurement)
        keep = random_core.fast_keep_mask(key, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, x / (1.0 - p), 0.0)
        return jnp.where(keep, x, 0.0)

    return apply_op("dropout", _dropout, random_core.next_key(), x,
                    p=float(p), mode=mode, axis=ax)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x

    def _ad(key, x, *, p):
        alpha = 1.6732632423543772
        scale = 1.0507009873554805
        alpha_p = -alpha * scale
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
        a = (1.0 / _pymath.sqrt((alpha_p ** 2 * p + 1) * (1 - p))) if p < 1 else 0.0
        b = -a * alpha_p * p
        return a * jnp.where(keep, x, alpha_p) + b

    return apply_op("alpha_dropout", _ad, random_core.next_key(), x, p=float(p))


# ------------------------------------------------------------- conv


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _conv_nd(x, w, b, *, stride, padding, dilation, groups, data_format, nd):
    chan_first = data_format in ("NCHW", "NCL", "NCDHW")
    if chan_first:
        dn_in = "NC" + "DHW"[3 - nd:]
        dn_out = dn_in
    else:
        dn_in = "N" + "DHW"[3 - nd:] + "C"
        dn_out = dn_in
    dn_kernel = "OI" + "DHW"[3 - nd:]
    if isinstance(padding, str):
        pad = padding.upper()  # SAME / VALID
    else:
        pad = [(p, p) for p in padding] if not isinstance(padding[0], (list, tuple)) \
            else [tuple(p) for p in padding]
    # no preferred_element_type: the MXU accumulates bf16 convs in fp32 in
    # hardware, and mixed primitive-output dtype breaks the conv transpose
    # rule under value_and_grad (cotangent fp32 vs bf16 operands)
    y = jax.lax.conv_general_dilated(
        x, w,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=(dn_in, dn_kernel, dn_out),
        feature_group_count=groups,
    )
    if b is not None:
        shape = [1] * y.ndim
        shape[1 if chan_first else -1] = b.size
        y = y + b.reshape(shape)
    return y


def _norm_padding(padding, nd):
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return (int(padding),) * nd
    flat = []
    for p in padding:
        if isinstance(p, (list, tuple)):
            flat.append(tuple(int(v) for v in p))
        else:
            flat.append(int(p))
    if len(flat) == 2 * nd and all(isinstance(p, int) for p in flat):
        # paddle allows [pad_h_top, pad_h_bottom, pad_w_left, pad_w_right]
        return tuple((flat[2 * i], flat[2 * i + 1]) for i in range(nd))
    return tuple(flat)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return apply_op(
        "conv1d", _conv_nd, x, weight, bias,
        stride=_pair(stride, 1), padding=_norm_padding(padding, 1),
        dilation=_pair(dilation, 1), groups=int(groups), data_format=data_format, nd=1)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """reference: operators/conv_op.cc (conv2d). Maps to one MXU conv."""
    return apply_op(
        "conv2d", _conv_nd, x, weight, bias,
        stride=_pair(stride), padding=_norm_padding(padding, 2),
        dilation=_pair(dilation), groups=int(groups), data_format=data_format, nd=2)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return apply_op(
        "conv3d", _conv_nd, x, weight, bias,
        stride=_pair(stride, 3), padding=_norm_padding(padding, 3),
        dilation=_pair(dilation, 3), groups=int(groups), data_format=data_format, nd=3)


def _causal_depthwise_conv1d(x, w, bias=None, *, activation):
    k = w.shape[0]
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    padded = jnp.pad(xf, ((0, 0), (k - 1, 0), (0, 0)))
    seq = x.shape[1]
    out = sum(padded[:, j:j + seq] * wf[j] for j in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if activation == "silu":
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


def causal_depthwise_conv1d(x, weight, activation=None, name=None,
                            bias=None):
    """The short convolution of linear-attention and state-space layers, on
    ``x`` [batch, seq, channels] as it leaves a projection (no transpose to
    ``conv1d``'s layout, no padding for the caller to get right): every
    channel its own ``weight`` [kernel, channels] taps over the positions
    t - kernel + 1 .. t (tap ``kernel - 1`` meets position t), zero history
    before a row's start; ``bias`` None or [channels], one number a channel
    added to the taps' sum (Mamba-2's convolution; at a row's first token it
    stands on a zero history like any other); ``activation`` None or 'silu',
    after the bias. The multiply-adds are float32 whatever ``x`` is, the
    result takes x's dtype."""
    if activation not in (None, "silu"):
        raise ValueError(f"activation must be None or 'silu', got "
                         f"{activation!r}")
    # without a bias, the call every layer made before there was one
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply_op("causal_depthwise_conv1d", _causal_depthwise_conv1d,
                    *args, activation=activation)


def _conv_transpose_nd(x, w, b, *, stride, padding, output_padding, dilation, groups,
                       data_format, nd):
    """Transposed conv as the explicit input-gradient construction:
    lhs-dilate x by stride, pad each spatial side by d·(k−1)−p (plus
    output_padding on the high side), and run a stride-1 conv with the
    spatially-flipped kernel. This reproduces the reference/torch output
    size (i−1)·s − 2p + d·(k−1) + 1 + op exactly for all channel counts
    (jax.lax.conv_transpose's padding convention differs, and its
    transpose_kernel path mis-contracts when in != out for the paddle
    [in, out, *k] weight layout).

    groups > 1 (reference conv_transpose_op.cc `groups` attr): the
    paddle weight [in, out/g, *k] stacks the per-group kernels along
    dim 0; rearranged to [in/g, g*(out/g), *k] it maps onto ONE XLA
    grouped conv (feature_group_count=g) — output block j uses input
    block j, exactly the per-group transpose."""
    chan_first = data_format in ("NCHW", "NCL", "NCDHW")
    sp = "DHW"[3 - nd:]
    dn_in = ("NC" + sp) if chan_first else ("N" + sp + "C")
    if isinstance(padding, str):
        if padding.upper() == "VALID":
            padding = [(0, 0)] * nd
        else:
            raise NotImplementedError(
                f"string padding {padding!r} for conv transpose (SAME is "
                f"ambiguous for transposed convs; pass explicit ints)")
    pads = [(p, p) if isinstance(p, int) else tuple(p) for p in padding]
    ksp = [w.shape[2 + i] for i in range(nd)]
    out_pad = output_padding if output_padding else (0,) * nd
    pad_cfg = [(dilation[i] * (ksp[i] - 1) - pads[i][0],
                dilation[i] * (ksp[i] - 1) - pads[i][1] + out_pad[i])
               for i in range(nd)]
    spatial_axes = tuple(range(2, 2 + nd))
    w_flipped = jnp.flip(w, axis=spatial_axes)
    if groups > 1:
        cin, og = w.shape[0], w.shape[1]
        if cin % groups:
            raise ValueError(f"in_channels {cin} not divisible by "
                             f"groups {groups}")
        wk = w_flipped.reshape((groups, cin // groups, og) + w.shape[2:])
        w_flipped = jnp.moveaxis(wk, 0, 1).reshape(
            (cin // groups, groups * og) + w.shape[2:])
    # kernel [in, out, *k]: contraction over dim0 (=I), outputs dim1 (=O)
    y = jax.lax.conv_general_dilated(
        x, w_flipped,
        window_strides=(1,) * nd,
        padding=pad_cfg,
        lhs_dilation=stride,
        rhs_dilation=dilation,
        dimension_numbers=(dn_in, "IO" + sp, dn_in),
        feature_group_count=int(groups),
    )
    if b is not None:
        shape = [1] * y.ndim
        shape[1 if chan_first else -1] = b.size
        y = y + b.reshape(shape)
    return y


def _resolve_output_padding(x, weight, output_size, output_padding, stride,
                            padding, dilation, nd, data_format):
    """Derive output_padding from a requested output_size (reference:
    conv_transpose_op.cc InferShape): op = out - ((i-1)s - 2p + d(k-1) + 1),
    valid when 0 <= op < stride."""
    if output_size is None:
        return _pair(output_padding, nd)
    if isinstance(padding, str):
        if padding.upper() != "VALID":
            raise NotImplementedError(
                f"output_size with string padding {padding!r}")
        padding = [(0, 0)] * nd
    sizes = list(output_size)[-nd:]
    chan_first = data_format in ("NCHW", "NCL", "NCDHW")
    xs = x.shape[2:2 + nd] if chan_first else x.shape[1:1 + nd]
    ks = weight.shape[2:2 + nd]
    ops = []
    for i in range(nd):
        p = padding[i]
        plo, phi = (p, p) if isinstance(p, int) else tuple(p)
        base = (xs[i] - 1) * stride[i] - plo - phi + \
            dilation[i] * (ks[i] - 1) + 1
        op = int(sizes[i]) - base
        if not 0 <= op < stride[i]:
            raise ValueError(
                f"output_size[{i}]={sizes[i]} unreachable: base {base}, "
                f"stride {stride[i]} (need base <= size < base+stride)")
        ops.append(op)
    return tuple(ops)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     dilation=1, groups=1, output_size=None,
                     data_format="NCHW", name=None):
    """reference: operators/conv_transpose_op.cc."""
    stride_, pad_, dil_ = _pair(stride), _norm_padding(padding, 2), _pair(dilation)
    op_ = _resolve_output_padding(x, weight, output_size, output_padding,
                                  stride_, pad_, dil_, 2, data_format)
    return apply_op(
        "conv2d_transpose", _conv_transpose_nd, x, weight, bias,
        stride=stride_, padding=pad_, output_padding=op_, dilation=dil_,
        groups=int(groups), data_format=data_format, nd=2)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0,
                     groups=1, dilation=1, output_size=None,
                     data_format="NCL", name=None):
    stride_, pad_, dil_ = (_pair(stride, 1), _norm_padding(padding, 1),
                           _pair(dilation, 1))
    op_ = _resolve_output_padding(x, weight, output_size, output_padding,
                                  stride_, pad_, dil_, 1, data_format)
    return apply_op(
        "conv1d_transpose", _conv_transpose_nd, x, weight, bias,
        stride=stride_, padding=pad_, output_padding=op_, dilation=dil_,
        groups=int(groups), data_format=data_format, nd=1)


# ------------------------------------------------------------- pooling


def _pool_geometry(x, ksize, stride, padding, ceil_mode, data_format, nd):
    """Shared window/stride/pad derivation for the pooling family."""
    chan_first = data_format in ("NCHW", "NCL", "NCDHW")
    if chan_first:
        window = (1, 1) + ksize
        strides = (1, 1) + stride
        spatial = tuple(range(2, 2 + nd))
    else:
        window = (1,) + ksize + (1,)
        strides = (1,) + stride + (1,)
        spatial = tuple(range(1, 1 + nd))
    if isinstance(padding, str):
        pads = padding.upper()  # reduce_window accepts "SAME"/"VALID"
        had_pad = padding.upper() == "SAME"
    else:
        sp_pads = [(p, p) if isinstance(p, int) else tuple(p)
                   for p in padding]
        if ceil_mode:
            # widen the high-side pad so the last (partial) window counts:
            # out_ceil = ceil((i + lo + hi - k)/s) + 1
            sp_pads = list(sp_pads)
            for i, ax in enumerate(spatial):
                span = x.shape[ax] + sp_pads[i][0] + sp_pads[i][1] - ksize[i]
                extra = (-span) % stride[i]
                sp_pads[i] = (sp_pads[i][0], sp_pads[i][1] + extra)
        pads = [(0, 0)] * x.ndim
        for i, ax in enumerate(spatial):
            pads[ax] = sp_pads[i]
        had_pad = any(p != (0, 0) for p in pads)
        pads = tuple(pads)
    return window, strides, pads, spatial, had_pad


def _spatial_index_array(x, spatial):
    """int32 array shaped like x holding each cell's flattened spatial
    index (reference pool_with_index mask semantics: the index within
    the input's flattened spatial dims, per sample and channel)."""
    sizes = [x.shape[a] for a in spatial]
    idx = jnp.arange(int(np.prod(sizes)), dtype=jnp.int32).reshape(sizes)
    shape = [1] * x.ndim
    for a, s in zip(spatial, sizes):
        shape[a] = s
    return jnp.broadcast_to(idx.reshape(shape), x.shape)


def _max_pool_with_index(x, *, ksize, stride, padding, ceil_mode,
                         data_format, nd):
    """Max pooling that also returns the argmax mask (reference:
    operators/pool_with_index_op.cc max_pool2d_with_index): a variadic
    reduce_window over (value, flat spatial index) pairs; ties take the
    smaller index, padding cells can never win."""
    window, strides, pads, spatial, _ = _pool_geometry(
        x, ksize, stride, padding, ceil_mode, data_format, nd)
    idx = _spatial_index_array(x, spatial)
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min

    def reducer(a, b):
        av, ai = a
        bv, bi = b
        take_b = (bv > av) | ((bv == av) & (bi < ai))
        return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)

    vals, mask = jax.lax.reduce_window(
        (x, idx), (jnp.asarray(neg, x.dtype), jnp.int32(2**31 - 1)),
        reducer, window, strides, pads)
    return vals, mask


def _pool_nd(x, *, ksize, stride, padding, mode, ceil_mode, data_format, nd,
             exclusive=True, divisor=None):
    window, strides, pads, spatial, had_pad = _pool_geometry(
        x, ksize, stride, padding, ceil_mode, data_format, nd)
    if mode == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    # avg
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if divisor is not None:
        return summed / float(divisor)
    # had_pad is a host bool derived from the static pool geometry (shape
    # arithmetic only), not from x's values
    if exclusive and had_pad:  # tracelint: disable=TPU001
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
        return summed / counts
    return summed / float(np.prod(ksize))


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW", name=None):
    ksize = _pair(kernel_size)
    stride = ksize if stride is None else _pair(stride)
    pad = _norm_padding(padding, 2)
    if return_mask:
        return apply_op("max_pool2d_index", _max_pool_with_index, x,
                        ksize=ksize, stride=stride, padding=pad,
                        ceil_mode=bool(ceil_mode),
                        data_format=data_format, nd=2)
    return apply_op("max_pool2d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=pad, mode="max", ceil_mode=bool(ceil_mode),
                    data_format=data_format, nd=2)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    ksize = _pair(kernel_size)
    stride = ksize if stride is None else _pair(stride)
    pad = _norm_padding(padding, 2)
    return apply_op("avg_pool2d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=pad, mode="avg", ceil_mode=bool(ceil_mode),
                    data_format=data_format, nd=2, exclusive=bool(exclusive),
                    divisor=divisor_override)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    ksize = _pair(kernel_size, 1)
    stride = ksize if stride is None else _pair(stride, 1)
    if return_mask:
        return apply_op("max_pool1d_index", _max_pool_with_index, x,
                        ksize=ksize, stride=stride,
                        padding=_norm_padding(padding, 1),
                        ceil_mode=bool(ceil_mode), data_format="NCL",
                        nd=1)
    return apply_op("max_pool1d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=_norm_padding(padding, 1), mode="max",
                    ceil_mode=bool(ceil_mode), data_format="NCL", nd=1)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    ksize = _pair(kernel_size, 1)
    stride = ksize if stride is None else _pair(stride, 1)
    return apply_op("avg_pool1d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=_norm_padding(padding, 1), mode="avg",
                    ceil_mode=bool(ceil_mode), data_format="NCL", nd=1,
                    exclusive=bool(exclusive))


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    axes = (2, 3) if data_format == "NCHW" else (1, 2)
    return apply_op("adaptive_avg_pool2d", _adaptive_pool_nd, x,
                    out_sizes=_pair(output_size), spatial_axes=axes,
                    mode="avg")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    if return_mask:
        return apply_op("adaptive_max_pool2d_index",
                        _adaptive_max_pool_with_index, x,
                        out_sizes=_pair(output_size), spatial_axes=(2, 3))
    return apply_op("adaptive_max_pool2d", _adaptive_pool_nd, x,
                    out_sizes=_pair(output_size), spatial_axes=(2, 3),
                    mode="max")


def adaptive_avg_pool1d(x, output_size, name=None):
    return apply_op("adaptive_avg_pool1d", _adaptive_pool_nd, x,
                    out_sizes=_pair(output_size, 1), spatial_axes=(2,),
                    mode="avg")


# ------------------------------------------------------------- norms


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None,
               name=None):
    """reference: operators/batch_norm_op.cc.

    ``_BatchNormBase.forward`` calls this in every mode, eager and traced.
    Training mode writes the updated running stats into the passed buffer
    Tensors: in place when eager; under a trace the values are tracers that
    the managed trace paths thread out of the program (see below).

    The batch statistics are two sums of ONE pass over ``x``, taken about
    the running mean ``c`` (a constant per channel, under ``stop_gradient``):
    ``mean = c + E[x-c]``, ``var = E[(x-c)^2] - E[x-c]^2``. That is the
    variance for every ``c``; the shift keeps the subtraction from
    cancelling (relative error of ``var`` about ``eps * (1 + (mean-c)^2 /
    var)``, and ``c`` tracks the batch mean). Two independent reductions
    fuse into the epilogue of the convolution that produced ``x``; the
    two-pass ``jnp.var`` read every activation a second time.
    """
    chan_ax = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != chan_ax)
    use_batch = training and not (use_global_stats or False)

    def _bn_infer(x, rm, rv, w, b, *, eps, chan_ax):
        shape = [1] * x.ndim
        shape[chan_ax] = -1
        inv = jax.lax.rsqrt(rv.reshape(shape) + eps)
        y = (x - rm.reshape(shape)) * inv
        if w is not None:
            y = y * w.reshape(shape)
        if b is not None:
            y = y + b.reshape(shape)
        return y

    if not use_batch:
        return apply_op("batch_norm_infer", _bn_infer, x, running_mean, running_var,
                        weight, bias, eps=float(epsilon), chan_ax=chan_ax)

    def _bn_train(x, w, b, rm, *, eps, axes, chan_ax):
        shape = [1] * x.ndim
        shape[chan_ax] = -1
        c = jax.lax.stop_gradient(rm).astype(x.dtype)
        c = jnp.where(jnp.isfinite(c), c, 0)  # a poisoned buffer stays out
        d = x - c.reshape(shape)
        s1 = jnp.mean(d, axis=axes)
        s2 = jnp.mean(d * d, axis=axes)
        mean = c + s1
        var = jnp.maximum(s2 - s1 * s1, 0)
        inv = jax.lax.rsqrt(var.reshape(shape) + eps)
        y = (x - mean.reshape(shape)) * inv
        if w is not None:
            y = y * w.reshape(shape)
        if b is not None:
            y = y + b.reshape(shape)
        return y, mean, var

    rm = (jnp.zeros((x.shape[chan_ax],), jnp.float32)
          if running_mean is None else running_mean)
    y, mean, var = apply_op("batch_norm_train", _bn_train, x, weight, bias, rm,
                            eps=float(epsilon), axes=axes, chan_ax=chan_ax)
    # update running stats (no grad). Under trace this writes tracers into
    # the buffer Tensors on purpose: the managed trace paths
    # (spmd.build_train_step forward_loss, jit static_function pure_fn)
    # snapshot+restore buffers around the trace and thread the updated
    # values out functionally, so the moving averages keep calibrating
    # inside compiled training steps instead of freezing at init.
    if isinstance(running_mean, Tensor):
        m = float(momentum)
        with _no_grad():
            stop = jax.lax.stop_gradient
            running_mean.set_value(m * running_mean._value +
                                   (1 - m) * stop(mean._value))
            running_var.set_value(m * running_var._value +
                                  (1 - m) * stop(var._value))
    return y


def _no_grad():
    from ..core.dispatch import no_grad_ctx

    return no_grad_ctx()


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    """reference: operators/layer_norm_op.cc."""
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))

    def _ln(x, w, b, *, eps, n_axes):
        axes = tuple(range(x.ndim - n_axes, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        if w is not None:
            y = y * w
        if b is not None:
            y = y + b
        return y

    return apply_op("layer_norm", _ln, x, weight, bias, eps=float(epsilon), n_axes=n_axes)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW",
                  name=None):
    def _in(x, w, b, *, eps):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        if w is not None:
            shape = (1, -1) + (1,) * (x.ndim - 2)
            y = y * w.reshape(shape)
        if b is not None:
            shape = (1, -1) + (1,) * (x.ndim - 2)
            y = y + b.reshape(shape)
        return y

    return apply_op("instance_norm", _in, x, weight, bias, eps=float(eps))


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def _gn(x, w, b, *, groups, eps):
        n, c = x.shape[0], x.shape[1]
        xg = x.reshape((n, groups, c // groups) + x.shape[2:])
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
        shape = (1, -1) + (1,) * (x.ndim - 2)
        if w is not None:
            y = y * w.reshape(shape)
        if b is not None:
            y = y + b.reshape(shape)
        return y

    return apply_op("group_norm", _gn, x, weight, bias, groups=int(num_groups),
                    eps=float(epsilon))


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW",
                        name=None):
    def _lrn(x, *, size, alpha, beta, k):
        sq = jnp.square(x)
        half = size // 2
        c = x.shape[1]
        pads = [(0, 0)] * x.ndim
        pads[1] = (half, size - half - 1)
        sq = jnp.pad(sq, pads)
        window = [1] * x.ndim
        window[1] = size
        s = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(window), (1,) * x.ndim,
                                  "VALID")
        # reference (nn/functional/norm.py local_response_norm) runs the
        # squared sum through avg_pool: the divisor is the window SIZE
        return x / jnp.power(k + alpha * s / size, beta)

    return apply_op("lrn", _lrn, x, size=int(size), alpha=float(alpha),
                    beta=float(beta), k=float(k))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply_op(
        "normalize",
        lambda x, *, p, axis, eps: x / jnp.maximum(
            jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True), 1.0 / p), eps),
        x, p=float(p), axis=int(axis), eps=float(epsilon))


# ------------------------------------------------------------- losses


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, name=None):
    """reference: operators/softmax_with_cross_entropy_op.cc."""

    def _ce(logits, label, weight, *, ignore_index, reduction, soft_label, axis,
            use_softmax):
        logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax else jnp.log(
            jnp.clip(logits, 1e-30, None))
        if soft_label:
            loss = -jnp.sum(label * logp, axis=axis)
            return _reduce_loss(loss, reduction)
        lbl = label.astype(jnp.int32)
        if lbl.ndim == logp.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        loss = -jnp.take_along_axis(logp, lbl[..., None], axis=axis)[..., 0]
        valid = lbl != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            wpc = jnp.take(weight, jnp.clip(lbl, 0, None), axis=0)
            loss = loss * jnp.where(valid, wpc, 0.0)
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, wpc, 0.0)), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
        return _reduce_loss(loss, reduction)

    return apply_op("cross_entropy", _ce, input, label, weight,
                    ignore_index=int(ignore_index), reduction=reduction,
                    soft_label=bool(soft_label), axis=int(axis),
                    use_softmax=bool(use_softmax))


def _lce_chunks(h, w, label, ignore_index, with_grads):
    """One pass over the token chunks ([C, c, H] hidden, [C, c] labels):
    the summed cross-entropy of ``h @ w`` and, with ``with_grads``, its
    gradients w.r.t. ``h`` and ``w`` — a chunk's logits live only inside
    its scan step. Logits, softmax and the sums are float32 whatever the
    operands' dtype (bf16 under amp: the matmuls accumulate in f32)."""
    f32 = jnp.float32

    def chunk(dw, xs):
        hc, lc = xs
        logits = jnp.dot(hc, w, preferred_element_type=f32)      # [c, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = lc != ignore_index
        safe = jnp.where(valid, lc, 0)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        loss = jnp.sum(jnp.where(valid, lse - picked, 0.0))
        if not with_grads:
            return dw, (loss, None)
        dlogits = jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
            safe, logits.shape[-1], dtype=f32)
        dlogits = jnp.where(valid[:, None], dlogits, 0.0).astype(hc.dtype)
        dh = jnp.dot(dlogits, w.T, preferred_element_type=f32)
        dw = dw + jnp.dot(hc.T, dlogits, preferred_element_type=f32)
        return dw, (loss, dh.astype(hc.dtype))

    dw0 = jnp.zeros(w.shape if with_grads else (), f32)
    dw, (losses, dh) = jax.lax.scan(chunk, dw0, (h, label))
    return jnp.sum(losses), dh, dw


@_pyfunctools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _lce_sum(h, w, label, ignore_index):
    return _lce_chunks(h, w, label, ignore_index, False)[0]


def _lce_sum_fwd(h, w, label, ignore_index):
    # the gradients are taken in the forward pass, while a chunk's logits
    # exist: nothing of size [tokens, vocab] is kept and nothing recomputed
    total, dh, dw = _lce_chunks(h, w, label, ignore_index, True)
    return total, (dh, dw.astype(w.dtype))


def _lce_sum_bwd(ignore_index, res, g):
    dh, dw = res
    return (dh * g.astype(dh.dtype)), (dw * g.astype(dw.dtype)), None


_lce_sum.defvjp(_lce_sum_fwd, _lce_sum_bwd)


def linear_cross_entropy(input, weight, label, ignore_index=-100,
                         chunk_size=2048, name=None):
    """Mean cross-entropy of the logits ``input @ weight`` against integer
    ``label`` without ever holding the logits whole: the head of a language
    model over a large vocabulary ([16,384 tokens, 50,304] is 3.3 GB in
    float32). ``input`` [..., H], ``weight`` [H, V] (an ``nn.Linear``'s),
    ``label`` [...]; positions whose label is ``ignore_index`` are left out
    of the mean. Tokens go through in chunks of ``chunk_size``; under
    differentiation each chunk's gradients are taken while its logits
    exist, so the backward pass is a scaling. Equal to
    ``cross_entropy(linear(input, weight), label)`` up to summation
    order."""

    def _lce(h, w, label, *, ignore_index, chunk_size):
        hdim = h.shape[-1]
        hf = h.reshape(-1, hdim)
        lf = label.reshape(-1).astype(jnp.int32)
        n = hf.shape[0]
        c = min(int(chunk_size), n)
        pad = -n % c
        if pad:
            hf = jnp.pad(hf, ((0, pad), (0, 0)))
            lf = jnp.pad(lf, (0, pad), constant_values=ignore_index)
        count = jnp.maximum(jnp.sum(lf != ignore_index), 1)
        total = _lce_sum(hf.reshape(-1, c, hdim), w, lf.reshape(-1, c),
                         ignore_index)
        return total / count.astype(jnp.float32)

    return apply_op("linear_cross_entropy", _lce, input, weight, label,
                    ignore_index=int(ignore_index),
                    chunk_size=int(chunk_size))


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    from .. import tensor as pt

    loss = pt.unsqueeze(loss, -1)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    def _nll(logp, label, weight, *, ignore_index, reduction):
        if logp.ndim > 2:
            # paddle layout [N, C, d1, ...]: move the class axis last
            logp = jnp.moveaxis(logp, 1, -1)
        lbl = label.astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
        valid = lbl != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            loss = loss * jnp.take(weight, jnp.clip(lbl, 0, None))
        if reduction == "mean":
            denom = jnp.sum(jnp.take(weight, jnp.clip(lbl, 0, None)) * valid) \
                if weight is not None else jnp.sum(valid.astype(loss.dtype))
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
        return _reduce_loss(loss, reduction)

    return apply_op("nll_loss", _nll, input, label, weight,
                    ignore_index=int(ignore_index), reduction=reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return apply_op(
        "mse_loss",
        lambda x, y, *, reduction: _reduce_loss(jnp.square(x - y), reduction),
        input, label, reduction=reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return apply_op(
        "l1_loss",
        lambda x, y, *, reduction: _reduce_loss(jnp.abs(x - y), reduction),
        input, label, reduction=reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def _sl1(x, y, *, reduction, delta):
        d = jnp.abs(x - y)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce_loss(loss, reduction)

    return apply_op("smooth_l1_loss", _sl1, input, label, reduction=reduction,
                    delta=float(delta))


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def _bce(p, y, w, *, reduction):
        p = jnp.clip(p, 1e-12, 1 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w is not None:
            loss = loss * w
        return _reduce_loss(loss, reduction)

    return apply_op("bce", _bce, input, label, weight, reduction=reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    def _bcel(x, y, w, pw, *, reduction):
        max_val = jnp.clip(-x, 0, None)
        if pw is not None:
            log_w = (pw - 1) * y + 1
            loss = (1 - y) * x + log_w * (jnp.log1p(jnp.exp(-jnp.abs(x))) + max_val)
        else:
            loss = (1 - y) * x + jnp.log1p(jnp.exp(-jnp.abs(x))) + max_val
        if w is not None:
            loss = loss * w
        return _reduce_loss(loss, reduction)

    return apply_op("bce_logits", _bcel, logit, label, weight, pos_weight,
                    reduction=reduction)


def kl_div(input, label, reduction="mean", name=None):
    def _kl(logp, y, *, reduction):
        loss = y * (jnp.log(jnp.clip(y, 1e-12, None)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce_loss(loss, reduction)

    return apply_op("kl_div", _kl, input, label, reduction=reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return apply_op(
        "margin_ranking_loss",
        lambda x, o, y, *, margin, reduction: _reduce_loss(
            jnp.clip(-y * (x - o) + margin, 0, None), reduction),
        input, other, label, margin=float(margin), reduction=reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply_op(
        "hinge_embedding_loss",
        lambda x, y, *, margin, reduction: _reduce_loss(
            jnp.where(y == 1, x, jnp.clip(margin - x, 0, None)), reduction),
        input, label, margin=float(margin), reduction=reduction)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    return apply_op(
        "cosine_similarity",
        lambda a, b, *, axis, eps: jnp.sum(a * b, axis=axis) / jnp.maximum(
            jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis), eps),
        x1, x2, axis=int(axis), eps=float(eps))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def _focal(x, y, norm, *, alpha, gamma, reduction):
        p = jax.nn.sigmoid(x)
        ce = (1 - y) * x + jnp.log1p(jnp.exp(-jnp.abs(x))) + jnp.clip(-x, 0, None)
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if norm is not None:
            loss = loss / norm
        return _reduce_loss(loss, reduction)

    return apply_op("sigmoid_focal_loss", _focal, logit, label, normalizer,
                    alpha=float(alpha), gamma=float(gamma), reduction=reduction)


def square_error_cost(input, label):
    return apply_op("square_error_cost", lambda x, y: jnp.square(x - y), input, label)


# ------------------------------------------------------------- attention


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None,
                                 window=None, scale=None):
    """Fused attention entry point. Uses the Pallas flash kernel on TPU when
    enabled (ops/pallas/flash_attention.py); otherwise a jnp reference that
    XLA fuses well. Layout: [batch, heads, seq, head_dim]. ``window`` (with
    ``is_causal``): a sliding window of that many keys up to the query's own
    position. ``scale``: what multiplies the scores before the softmax;
    None is ``head_dim ** -0.5``."""
    from ..ops import attention as attn_ops

    return attn_ops.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=dropout_p, is_causal=is_causal,
        training=training, window=window, scale=scale)


# ------------------------------------------------------------- vision misc


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
                align_mode=0, data_format="NCHW", name=None):
    """reference: operators/interpolate_v2_op.cc (subset: nearest/bilinear)."""
    if size is not None and scale_factor is not None:
        raise ValueError("interpolate: pass exactly one of size / scale_factor")
    if size is None and scale_factor is None:
        raise ValueError("interpolate: one of size / scale_factor is required")
    if size is not None:
        size = _pair(size) if not isinstance(size, int) else (size, size)
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor,) * 2
        in_h, in_w = (x.shape[2], x.shape[3]) if data_format == "NCHW" else (x.shape[1], x.shape[2])
        size = (int(in_h * sf[0]), int(in_w * sf[1]))

    def _interp(x, *, size, mode, align_corners, chan_first):
        if chan_first:
            n, c, h, w = x.shape
            img = jnp.transpose(x, (0, 2, 3, 1))
        else:
            n, h, w, c = x.shape
            img = x
        oh, ow = size

        def src_pos(o, i_sz):
            pos = jnp.arange(o, dtype=jnp.float32)
            if align_corners:
                # out==1: reference uses ratio 0 -> sample index 0
                return pos * (float(i_sz - 1) / float(o - 1)) if o > 1 \
                    else jnp.zeros((1,), jnp.float32)
            return jnp.clip((pos + 0.5) * (i_sz / o) - 0.5, 0.0,
                            float(i_sz - 1))

        if mode == "bilinear":
            # exact half-pixel / align-corners sampling (reference:
            # interpolate_v2 bilinear kernel; jax.image.resize's
            # antialiased kernel diverges on downscale)
            si = src_pos(oh, h)
            sj = src_pos(ow, w)
            i0 = jnp.floor(si).astype(jnp.int32)
            j0 = jnp.floor(sj).astype(jnp.int32)
            i1 = jnp.minimum(i0 + 1, h - 1)
            j1 = jnp.minimum(j0 + 1, w - 1)
            wi = (si - i0)[None, :, None, None]
            wj = (sj - j0)[None, None, :, None]
            top = jnp.take(img, i0, axis=1)
            bot = jnp.take(img, i1, axis=1)
            tl, tr = jnp.take(top, j0, axis=2), jnp.take(top, j1, axis=2)
            bl, br = jnp.take(bot, j0, axis=2), jnp.take(bot, j1, axis=2)
            out = ((1 - wi) * ((1 - wj) * tl + wj * tr)
                   + wi * ((1 - wj) * bl + wj * br))
        elif mode == "nearest":
            if align_corners:
                # reference rounds half UP (int(ratio*i + 0.5)), not
                # banker's-round
                i_idx = jnp.floor(src_pos(oh, h) + 0.5).astype(jnp.int32)
                j_idx = jnp.floor(src_pos(ow, w) + 0.5).astype(jnp.int32)
            else:
                # floor(i * in/out) in INTEGER arithmetic: float32
                # h/oh can land just below an exact boundary
                i_idx = (jnp.arange(oh, dtype=jnp.int32) * h) // oh
                j_idx = (jnp.arange(ow, dtype=jnp.int32) * w) // ow
            out = jnp.take(jnp.take(img, i_idx, axis=1), j_idx, axis=2)
        else:  # bicubic / area via XLA resize
            method = {"bicubic": "cubic", "area": "linear"}[mode]
            out = jax.image.resize(img, (n, oh, ow, c), method=method)
        if chan_first:
            out = jnp.transpose(out, (0, 3, 1, 2))
        return out.astype(x.dtype)

    return apply_op("interpolate", _interp, x, size=tuple(size), mode=mode,
                    align_corners=bool(align_corners), chan_first=data_format == "NCHW")


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    def _ps(x, *, r):
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
        return x.reshape(n, c // (r * r), h * r, w * r)

    return apply_op("pixel_shuffle", _ps, x, r=int(upscale_factor))


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """reference: operators/unfold_op.cc (im2col)."""
    ks = _pair(kernel_sizes)
    st = _pair(strides)
    pd = _pair(paddings)
    dl = _pair(dilations)

    def _unfold(x, *, ks, st, pd, dl):
        n, c, h, w = x.shape
        patches = jax.lax.conv_general_dilated_patches(
            x, filter_shape=ks, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # patches: [n, c*kh*kw, oh, ow]
        return patches.reshape(n, patches.shape[1], -1)

    return apply_op("unfold", _unfold, x, ks=ks, st=st, pd=pd, dl=dl)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True,
                name=None):
    def _gs(x, grid, *, align_corners):
        n, c, h, w = x.shape
        gx = (grid[..., 0] + 1) * (w - 1) / 2 if align_corners else \
            ((grid[..., 0] + 1) * w - 1) / 2
        gy = (grid[..., 1] + 1) * (h - 1) / 2 if align_corners else \
            ((grid[..., 1] + 1) * h - 1) / 2
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        x1 = x0 + 1
        y1 = y0 + 1

        def sample(ix, iy):
            ixc = jnp.clip(ix, 0, w - 1).astype(jnp.int32)
            iyc = jnp.clip(iy, 0, h - 1).astype(jnp.int32)
            valid = ((ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1))
            batch = jnp.arange(n)[:, None, None]
            vals = x[batch, :, iyc, ixc]  # [n, gh, gw, c]
            return jnp.where(valid[..., None], vals, 0.0)

        wa = ((x1 - gx) * (y1 - gy))[..., None]
        wb = ((x1 - gx) * (gy - y0))[..., None]
        wc = ((gx - x0) * (y1 - gy))[..., None]
        wd = ((gx - x0) * (gy - y0))[..., None]
        out = (sample(x0, y0) * wa + sample(x0, y1) * wb + sample(x1, y0) * wc +
               sample(x1, y1) * wd)
        return jnp.transpose(out, (0, 3, 1, 2))

    return apply_op("grid_sample", _gs, x, grid, align_corners=bool(align_corners))


def affine_grid(theta, out_shape, align_corners=True, name=None):
    shape = tuple(int(s) for s in (out_shape.numpy() if isinstance(out_shape, Tensor)
                                   else out_shape))

    def _ag(theta, *, shape, align_corners):
        n, c, h, w = shape
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) * 2 + 1) / h - 1
            xs = (jnp.arange(w) * 2 + 1) / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        base = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # [h, w, 3]
        return jnp.einsum("nij,hwj->nhwi", theta, base)

    return apply_op("affine_grid", _ag, theta, shape=shape,
                    align_corners=bool(align_corners))


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None, data_format="NCHW"):
    def _ts(x, *, seg, ratio):
        nt, c, h, w = x.shape
        n = nt // seg
        xr = x.reshape(n, seg, c, h, w)
        fold = int(c * ratio)
        out_a = jnp.concatenate([xr[:, 1:, :fold], jnp.zeros_like(xr[:, :1, :fold])], axis=1)
        out_b = jnp.concatenate([jnp.zeros_like(xr[:, :1, fold:2 * fold]),
                                 xr[:, :-1, fold:2 * fold]], axis=1)
        out_c = xr[:, :, 2 * fold:]
        return jnp.concatenate([out_a, out_b, out_c], axis=2).reshape(nt, c, h, w)

    return apply_op("temporal_shift", _ts, x, seg=int(seg_num), ratio=float(shift_ratio))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def _npair(a, p, y, *, l2):
        sim = a @ p.T
        n = a.shape[0]
        eq = (y[:, None] == y[None, :]).astype(a.dtype)
        tgt = eq / jnp.sum(eq, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        ce = -jnp.mean(jnp.sum(tgt * logp, axis=1))
        reg = l2 * (jnp.mean(jnp.sum(jnp.square(a), axis=1)) +
                    jnp.mean(jnp.sum(jnp.square(p), axis=1))) / 2
        return ce + reg

    return apply_op("npair_loss", _npair, anchor, positive, labels, l2=float(l2_reg))


def glu(x, axis=-1, name=None):
    def _glu(x, *, axis):
        a, b = jnp.split(x, 2, axis=axis)
        return a * jax.nn.sigmoid(b)

    return apply_op("glu", _glu, x, axis=int(axis))


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ..tensor.manipulation import pad as _pad

    return _pad(x, pad, mode, value, data_format)


def unstack(x, axis=0, num=None):
    from ..tensor.manipulation import unstack as _unstack

    return _unstack(x, axis, num)


def diag_embed(input, offset=0, dim1=-2, dim2=-1):
    def _de(x, *, offset):
        return jax.vmap(lambda row: jnp.diag(row, k=offset))(x.reshape(-1, x.shape[-1])) \
            .reshape(x.shape[:-1] + (x.shape[-1] + abs(offset), x.shape[-1] + abs(offset)))

    return apply_op("diag_embed", _de, input, offset=int(offset))


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    if maxlen is None:
        maxlen = int(np.asarray(x._value).max())

    def _sm(x, *, maxlen, dtype):
        from ..core.dtype import convert_dtype

        r = jnp.arange(maxlen)
        return (r[None, :] < x[..., None]).astype(convert_dtype(dtype))

    return apply_op("sequence_mask", _sm, x, maxlen=int(maxlen), dtype=str(dtype))


# ---------------------------------------------------- 3-D pooling family
# (reference: operators/pool_op.cc 3-D kernels + adaptive variants; all
# ride the generic _pool_nd reduce_window path)


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    ksize = _pair(kernel_size, 3)
    stride = ksize if stride is None else _pair(stride, 3)
    pad = _norm_padding(padding, 3)
    if return_mask:
        return apply_op("max_pool3d_index", _max_pool_with_index, x,
                        ksize=ksize, stride=stride, padding=pad,
                        ceil_mode=bool(ceil_mode),
                        data_format=data_format, nd=3)
    return apply_op("max_pool3d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=pad, mode="max", ceil_mode=bool(ceil_mode),
                    data_format=data_format, nd=3)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    ksize = _pair(kernel_size, 3)
    stride = ksize if stride is None else _pair(stride, 3)
    pad = _norm_padding(padding, 3)
    return apply_op("avg_pool3d", _pool_nd, x, ksize=ksize, stride=stride,
                    padding=pad, mode="avg", ceil_mode=bool(ceil_mode),
                    data_format=data_format, nd=3, exclusive=bool(exclusive),
                    divisor=divisor_override)


def _adaptive_pool_nd(x, *, out_sizes, spatial_axes, mode):
    """General adaptive pooling: divisible fast path via reduce_window,
    else static per-bin reduction (shapes are compile-time constants)."""
    reducer = jnp.max if mode == "max" else jnp.mean
    in_sizes = [x.shape[a] for a in spatial_axes]
    if all(i % o == 0 for i, o in zip(in_sizes, out_sizes)):
        window = [1] * x.ndim
        for a, i, o in zip(spatial_axes, in_sizes, out_sizes):
            window[a] = i // o
        if mode == "max":
            return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                         tuple(window), tuple(window),
                                         "VALID")
        y = jax.lax.reduce_window(x, 0.0, jax.lax.add, tuple(window),
                                  tuple(window), "VALID")
        return y / float(np.prod([window[a] for a in spatial_axes]))

    def bins(i, o):
        # start = floor(k*i/o), end = CEIL((k+1)*i/o): bins may overlap
        # (reference adaptive pool kernel / AdaptiveStartIndex-EndIndex)
        return [((k * i) // o, -((-(k + 1) * i) // o)) for k in range(o)]

    def rec(axis_idx, slices):
        if axis_idx == len(spatial_axes):
            sl = [slice(None)] * x.ndim
            for a, (lo, hi) in zip(spatial_axes, slices):
                sl[a] = slice(lo, hi)
            return reducer(x[tuple(sl)], axis=tuple(spatial_axes),
                           keepdims=True)
        parts = [rec(axis_idx + 1, slices + [b])
                 for b in bins(in_sizes[axis_idx], out_sizes[axis_idx])]
        return jnp.concatenate(parts, axis=spatial_axes[axis_idx])

    return rec(0, [])


def _adaptive_bins(i, o):
    """start = floor(k*i/o), end = ceil((k+1)*i/o) — the reference
    adaptive pool bin boundaries (AdaptiveStartIndex/EndIndex)."""
    return [((k * i) // o, -((-(k + 1) * i) // o)) for k in range(o)]


def _adaptive_max_pool_with_index(x, *, out_sizes, spatial_axes):
    """Adaptive max pool returning (values, mask of flat spatial argmax)
    — reference operators/pool_with_index_op.cc (max_pool*_with_index
    adaptive=True). Bin shapes are compile-time constants, so each
    output cell is a static slice + argmax; ties take the first (lowest
    index) element like the reference kernels."""
    in_sizes = [x.shape[a] for a in spatial_axes]
    nd = len(spatial_axes)
    all_bins = [_adaptive_bins(i, o) for i, o in zip(in_sizes, out_sizes)]

    def rec(axis_idx, slices):
        if axis_idx == nd:
            sl = [slice(None)] * x.ndim
            for a, (lo, hi) in zip(spatial_axes, slices):
                sl[a] = slice(lo, hi)
            region = x[tuple(sl)]
            lead = region.shape[:spatial_axes[0]]
            rs = [region.shape[a] for a in spatial_axes]
            flat = region.reshape(lead + (-1,))
            loc = jnp.argmax(flat, axis=-1)
            val = jnp.take_along_axis(flat, loc[..., None], axis=-1)
            coords = jnp.unravel_index(loc, rs)
            glob = jnp.zeros_like(loc)
            for c, (lo, _), size in zip(coords, slices, in_sizes):
                glob = glob * size + (c + lo)
            keep = (1,) * nd
            return (val.reshape(lead + keep),
                    glob.astype(jnp.int32).reshape(lead + keep))
        parts = [rec(axis_idx + 1, slices + [b])
                 for b in all_bins[axis_idx]]
        return tuple(jnp.concatenate(p, axis=spatial_axes[axis_idx])
                     for p in zip(*parts))

    return rec(0, [])


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    out = _pair(output_size, 3)
    axes = (2, 3, 4) if data_format == "NCDHW" else (1, 2, 3)
    return apply_op("adaptive_avg_pool3d", _adaptive_pool_nd, x,
                    out_sizes=out, spatial_axes=axes, mode="avg")


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    out = _pair(output_size, 3)
    if return_mask:
        return apply_op("adaptive_max_pool3d_index",
                        _adaptive_max_pool_with_index, x,
                        out_sizes=out, spatial_axes=(2, 3, 4))
    return apply_op("adaptive_max_pool3d", _adaptive_pool_nd, x,
                    out_sizes=out, spatial_axes=(2, 3, 4), mode="max")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    if return_mask:
        return apply_op("adaptive_max_pool1d_index",
                        _adaptive_max_pool_with_index, x,
                        out_sizes=_pair(output_size, 1),
                        spatial_axes=(2,))
    return apply_op("adaptive_max_pool1d", _adaptive_pool_nd, x,
                    out_sizes=_pair(output_size, 1), spatial_axes=(2,),
                    mode="max")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    """reference: operators/conv_transpose_op.cc (3-D)."""
    stride_, pad_, dil_ = (_pair(stride, 3), _norm_padding(padding, 3),
                           _pair(dilation, 3))
    op_ = _resolve_output_padding(x, weight, output_size, output_padding,
                                  stride_, pad_, dil_, 3, data_format)
    return apply_op(
        "conv3d_transpose", _conv_transpose_nd, x, weight, bias,
        stride=stride_, padding=pad_, output_padding=op_, dilation=dil_,
        groups=int(groups), data_format=data_format, nd=3)


# --------------------------------------------------- small activations


def thresholded_relu(x, threshold=1.0, name=None):
    return apply_op("thresholded_relu",
                    lambda x, *, t: jnp.where(x > t, x, 0.0).astype(x.dtype),
                    x, t=float(threshold))


def _inplace_unary(fn):
    def inner(x, *args, **kwargs):
        x._assign_result(fn(x, *args, **kwargs))
        return x

    inner.__name__ = fn.__name__ + "_"
    inner.__doc__ = f"In-place variant of F.{fn.__name__}."
    return inner


relu_ = _inplace_unary(relu)
elu_ = _inplace_unary(elu)
tanh_ = _inplace_unary(tanh)
softmax_ = _inplace_unary(softmax)


# --------------------------------------------------------- extra losses


def bilinear(x1, x2, weight, bias=None, name=None):
    """Bilinear tensor product [B,in1]x[out,in1,in2]x[B,in2] -> [B,out]
    (reference: operators/bilinear_tensor_product_op.cc)."""

    def _bil(x1, x2, w, b):
        y = jnp.einsum("bi,oij,bj->bo", x1, w, x2)
        return y if b is None else y + b

    return apply_op("bilinear", _bil, x1, x2, weight, bias)


def dice_loss(input, label, epsilon=1e-5, name=None):
    """reference: fluid/layers/nn.py dice_loss — 1 - 2|X∩Y|/(|X|+|Y|),
    label one-hotted over input's last dim."""

    def _dice(x, y, *, eps):
        oh = jax.nn.one_hot(y[..., 0], x.shape[-1], dtype=x.dtype)
        reduce_dims = tuple(range(1, x.ndim))
        inter = jnp.sum(x * oh, axis=reduce_dims)
        union = jnp.sum(x, axis=reduce_dims) + jnp.sum(oh, axis=reduce_dims)
        return jnp.mean(1.0 - (2.0 * inter + eps) / (union + eps))

    return apply_op("dice_loss", _dice, input, label, eps=float(epsilon))


def log_loss(input, label, epsilon=1e-4, name=None):
    """reference: operators/log_loss_op.cc — elementwise negative log
    likelihood of a probability: -y*log(p+eps) - (1-y)*log(1-p+eps)."""

    def _ll(p, y, *, eps):
        return -(y * jnp.log(p + eps) + (1.0 - y) * jnp.log(1.0 - p + eps))

    return apply_op("log_loss", _ll, input, label, eps=float(epsilon))


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (reference: nn/functional/loss.py:1000 → warpctc op).

    log_probs: [T, B, C]; labels: [B, L] int; per-sample lengths.
    Log-domain alpha recursion over the extended label sequence
    (Graves 2006) as a lax.scan — TPU-native replacement for warp-ctc.
    log_softmax is applied internally (idempotent on already-normalized
    inputs, so both raw-logit and log-prob conventions work)."""

    def _ctc(lp, lab, in_len, lab_len, *, blank, norm_by_times):
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        T, B, C = lp.shape
        L = lab.shape[1]
        S = 2 * L + 1
        neg_inf = jnp.asarray(-1e30, jnp.float32)
        # extended labels: [blank, l1, blank, l2, ..., blank]
        ext = jnp.full((B, S), blank, dtype=lab.dtype)
        ext = ext.at[:, 1::2].set(lab)
        # allow the s-2 skip where ext[s] != blank and ext[s] != ext[s-2]
        ext_prev2 = jnp.pad(ext, ((0, 0), (2, 0)),
                            constant_values=blank)[:, :S]
        can_skip = (ext != blank) & (ext != ext_prev2)
        pos = jnp.arange(S)[None, :]

        def emit(t_lp):  # [B, S] log p_t(ext_s)
            return jnp.take_along_axis(t_lp, ext, axis=1)

        alpha0 = jnp.full((B, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(emit(lp[0])[:, 0])
        alpha0 = alpha0.at[:, 1].set(jnp.where(lab_len > 0,
                                               emit(lp[0])[:, 1], neg_inf))

        def step(alpha, t_lp):
            a1 = jnp.pad(alpha, ((0, 0), (1, 0)),
                         constant_values=-1e30)[:, :S]
            a2 = jnp.pad(alpha, ((0, 0), (2, 0)),
                         constant_values=-1e30)[:, :S]
            a2 = jnp.where(can_skip, a2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2)
            return merged + emit(t_lp), None

        def scan_step(carry, xs):
            alpha, t = carry
            new_alpha, _ = step(alpha, xs)
            # freeze alpha once t >= input_length (per sample)
            live = (t < in_len)[:, None]
            return (jnp.where(live, new_alpha, alpha), t + 1), None

        (alpha, _), _ = jax.lax.scan(scan_step, (alpha0, jnp.asarray(1)),
                                     lp[1:])
        # final: logaddexp of positions 2*lab_len and 2*lab_len - 1
        sl = 2 * lab_len
        last = jnp.take_along_axis(alpha, sl[:, None], axis=1)[:, 0]
        prev = jnp.take_along_axis(
            alpha, jnp.maximum(sl - 1, 0)[:, None], axis=1)[:, 0]
        ll = jnp.where(lab_len > 0, jnp.logaddexp(last, prev), last)
        loss = -ll
        if norm_by_times:
            loss = loss / jnp.maximum(in_len.astype(loss.dtype), 1.0)
        return loss

    out = apply_op("ctc_loss", _ctc, log_probs, labels, input_lengths,
                   label_lengths, blank=int(blank),
                   norm_by_times=bool(norm_by_times))
    if reduction == "mean":
        # reference semantics: per-sample loss divided by label length,
        # then batch-meaned
        return apply_op(
            "ctc_mean",
            lambda l, n: jnp.mean(l / jnp.maximum(
                n.astype(l.dtype), 1.0)), out, label_lengths)
    if reduction == "sum":
        from .. import tensor as pt

        return pt.sum(out)
    return out


@_pyfunctools.lru_cache(maxsize=32)
def _hsigmoid_default_tree(num_classes):
    """Complete-binary-heap path tables for the default hsigmoid tree:
    (table, code, mask) numpy arrays [num_classes, depth], built once per
    num_classes and passed as positional (traced) args — rebuilding and
    hashing them per call would dominate the op at large class counts."""
    depth = int(np.ceil(np.log2(max(num_classes, 2))))
    table = np.zeros((num_classes, depth), np.int64)
    code = np.zeros((num_classes, depth), np.float32)
    mask = np.zeros((num_classes, depth), np.float32)
    for c in range(num_classes):
        node = c + num_classes
        path = []
        while node > 1:
            path.append((node // 2, float(node & 1)))
            node //= 2
        path.reverse()
        for d, (n, bit) in enumerate(path):
            table[c, d] = n - 1   # weight row (internal nodes 1-based)
            code[c, d] = bit
            mask[c, d] = 1.0
    return table, code, mask


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference: nn/functional/loss.py
    hsigmoid_loss → hierarchical_sigmoid_op). Default tree: complete
    binary heap over num_classes leaves (internal nodes 1..K-1, leaf c =
    c + num_classes in heap numbering); custom trees via
    path_table/path_code [B, D]."""
    if path_table is None:
        table, code, mask = _hsigmoid_default_tree(int(num_classes))

        def _hs(x, lab, w, b, table, code, mask):
            if lab.ndim == 2:                    # paddle-convention [N, 1]
                lab = lab[:, 0]
            t = table[lab]                       # [B, D] weight rows
            cd = code[lab]                       # [B, D] targets
            mk = mask[lab]                       # [B, D] valid steps
            wrows = w[t]                         # [B, D, F]
            logits = jnp.einsum("bdf,bf->bd", wrows, x)
            if b is not None:
                logits = logits + b.reshape(-1)[t]
            # BCE with logits against the path code, masked
            per = jnp.maximum(logits, 0) - logits * cd + \
                jnp.log1p(jnp.exp(-jnp.abs(logits)))
            return jnp.sum(per * mk, axis=1, keepdims=True)

        return apply_op("hsigmoid_loss", _hs, input, label, weight, bias,
                        table, code, mask)

    def _hs_custom(x, lab, w, b, pt_, pc):
        if lab.ndim == 2:
            lab = lab[:, 0]
        valid = (pt_ >= 0).astype(x.dtype)
        rows = jnp.maximum(pt_, 0)
        wrows = w[rows]
        logits = jnp.einsum("bdf,bf->bd", wrows, x)
        if b is not None:
            logits = logits + b.reshape(-1)[rows]
        cd = pc.astype(x.dtype)
        per = jnp.maximum(logits, 0) - logits * cd + \
            jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return jnp.sum(per * valid, axis=1, keepdims=True)

    return apply_op("hsigmoid_loss_custom", _hs_custom, input, label,
                    weight, bias, path_table, path_code)


def gather_tree(ids, parents):
    """Beam-search ancestry walk (reference: operators/gather_tree_op.cc):
    ids/parents [T, B, beam]; returns the full sequences obtained by
    backtracking each final beam through its parent pointers."""

    def _gt(ids, parents):
        T = ids.shape[0]
        beams = jnp.arange(ids.shape[2])[None, :] * jnp.ones(
            (ids.shape[1], 1), ids.dtype)

        def back(carry, xs):
            beam_idx = carry
            step_ids, step_parents = xs
            out = jnp.take_along_axis(step_ids, beam_idx, axis=1)
            nxt = jnp.take_along_axis(step_parents, beam_idx, axis=1)
            return nxt, out

        _, rev = jax.lax.scan(back, beams.astype(ids.dtype),
                              (ids[::-1], parents[::-1]))
        return rev[::-1]

    return apply_op("gather_tree", _gt, ids, parents)
