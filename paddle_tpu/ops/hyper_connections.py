"""Manifold-constrained hyper-connections (mHC: DeepSeek-AI,
arXiv:2512.24880, on top of Hyper-Connections, arXiv:2409.19606): a decoder
whose residual path is ``n`` streams a token, mixed around every sublayer by
three maps computed from the streams themselves.

The streams ride as ONE lane-dense array ``[B, T, n * C]`` — ``vec(X)``,
stream i the features ``[i C, (i + 1) C)`` — so a stream is a slice of whole
lane groups and nothing is ever viewed as ``[.., n, C]`` (on a TPU a
4-row minor dimension pads to a tile's 8 or 16 sublanes). For a sublayer F
with parameters ``phi [n C, 2 n + n^2]``, ``b [2 n + n^2]`` and
``alpha = (pre, post, res)``:

1. ``maps``      ``x~ = vec(X) / sqrt(mean(vec(X)^2) + eps)``;
                 ``[h_pre | h_post | h_res] = x~ phi`` (float32 IN EARNEST:
                 ``Precision.HIGHEST``, as the expert layer's router), each
                 part times its alpha plus its bias      -> [B, T, 2 n + n^2]
2. ``coefficients``  ``H_pre = sigmoid(.)``, ``H_post = 2 sigmoid(.)``,
                 ``H_res = sinkhorn_knopp(clamp(.))``: ``exp``, then
                 ``iters`` rounds of (rows / (row sums + eps), columns /
                 (column sums + eps)) — EVERY round runs, there is no test
                 for convergence — a doubly stochastic ``n x n`` a token,
                 row-major ``[B, T, n^2]``
3. ``pre``       ``u = H_pre X`` — what the sublayer's norm reads, [B, T, C]
4. ``post``      ``X <- H_res X + H_post^T y`` with ``y = F(norm(u))``

``expand`` replicates a hidden state to the n streams (after the embedding),
``reduce`` sums them (before the final norm).

All of it is float32 arithmetic on arrays as large as the streams (a
``[4096, 14336]`` float32 array is 235 MB), so every stage is a
``jax.checkpoint`` of its own, as the KDA stages are: a differentiated
program keeps a stage's inputs and rebuilds what it needs inside it. The
Sinkhorn rounds run with the TOKENS in the minor dimension (``[n, n, B T]``:
a token's 4 x 4 matrix would be a 64th of a tile) and their sums are written
as adds of slices, so that the twenty rounds are element-wise work XLA can
fuse (as n x n separate vectors they fuse further on a TPU, and XLA's CPU
compiler does not finish: PR 53). What ran counts in
``paddle_tpu_mhc_total{path}`` (``xla`` today: the label a later kernel
changes) and ``paddle_tpu_mhc_sinkhorn_rounds``.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics

_MHC_TOTAL = obs_metrics.counter(
    "paddle_tpu_mhc_total",
    "hyper-connected sublayers (maps, pre and post mixes of the residual "
    "streams) by the path their stages took (xla: XLA operations under a "
    "jax.checkpoint a stage); one count per traced sublayer call",
    labelnames=("path",))

_SINKHORN_ROUNDS = obs_metrics.gauge(
    "paddle_tpu_mhc_sinkhorn_rounds",
    "Sinkhorn-Knopp rounds (one row and one column normalisation each) the "
    "last traced hyper-connected sublayer ran for its residual mixing "
    "matrix: all of them, no early exit")


def count_sublayer(iters):
    """One hyper-connected sublayer traced, with ``iters`` Sinkhorn rounds."""
    _MHC_TOTAL.inc(path="xla")
    _SINKHORN_ROUNDS.set(iters)


def _sum_slices(m, axis):
    """``m.sum(axis, keepdims=True)`` as adds of slices (a static, small
    extent): element-wise, so a chain of rounds fuses."""
    parts = [jax.lax.slice_in_dim(m, i, i + 1, axis=axis)
             for i in range(m.shape[axis])]
    total = parts[0]
    for part in parts[1:]:
        total = total + part
    return total


def sinkhorn_knopp(logits, *, n, iters, eps, clamp=None):
    """``[..., n * n]`` (row-major n x n, any float dtype) -> the same shape
    in float32: ``exp`` of the logits (clamped to ``clamp = (low, high)``
    first), then ``iters`` rounds of rows over (row sums + eps) and columns
    over (column sums + eps). With ``iters=0``: the un-projected ``exp``."""
    def project(logits):
        lead = logits.shape[:-1]
        m = logits.astype(jnp.float32).reshape(-1, n, n)
        m = jnp.moveaxis(m, 0, -1)                    # [n, n, tokens]
        if clamp is not None:
            m = jnp.clip(m, clamp[0], clamp[1])
        m = jnp.exp(m)
        for _ in range(iters):
            m = m / (_sum_slices(m, 1) + eps)         # a row's entries
            m = m / (_sum_slices(m, 0) + eps)         # a column's
        return jnp.moveaxis(m, -1, 0).reshape(*lead, n * n)

    return jax.checkpoint(project)(logits)


def maps(streams, phi, b, alpha, *, n, eps):
    """Steps 1-2's linear part: streams [B, T, n C] -> the three maps'
    pre-activations [B, T, 2 n + n^2] in float32, ``[pre | post | res]``,
    each already ``alpha_part * h + b``."""
    def stage(streams, phi, b, alpha):
        f32 = jnp.float32
        x = streams.astype(f32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        h = jnp.dot(x, phi.astype(f32), precision=jax.lax.Precision.HIGHEST)
        scale = jnp.repeat(alpha.astype(f32), np.array([n, n, n * n]),
                           total_repeat_length=2 * n + n * n)
        return h * scale + b.astype(f32)

    return jax.checkpoint(stage)(streams, phi, b, alpha)


def coefficients(h, *, n, iters, eps, clamp=None):
    """Step 3: the pre-activations [B, T, 2 n + n^2] -> (H_pre [B, T, n],
    H_post [B, T, n], H_res [B, T, n^2] row-major), float32."""
    h = h.astype(jnp.float32)
    return (jax.nn.sigmoid(h[..., :n]), 2.0 * jax.nn.sigmoid(h[..., n:2 * n]),
            sinkhorn_knopp(h[..., 2 * n:], n=n, iters=iters, eps=eps,
                           clamp=clamp))


def _stream(x, i, n):
    c = x.shape[-1] // n
    return x[..., i * c:(i + 1) * c]


def pre(streams, h_pre, *, n):
    """``u = H_pre X``: streams [B, T, n C] and H_pre [B, T, n] (float32)
    -> [B, T, C] in the streams' dtype (the sum in float32)."""
    def stage(streams, h_pre):
        u = sum(h_pre[..., i:i + 1]
                * _stream(streams, i, n).astype(jnp.float32)
                for i in range(n))
        return u.astype(streams.dtype)

    return jax.checkpoint(stage)(streams, h_pre)


def post(streams, y, h_post, h_res, *, n):
    """``X <- H_res X + H_post^T y``: streams [B, T, n C], the sublayer's
    output y [B, T, C], H_post [B, T, n], H_res [B, T, n^2] (float32) -> the
    new streams, in the streams' dtype (products and sums in float32)."""
    def stage(streams, y, h_post, h_res):
        f32 = jnp.float32
        yf = y.astype(f32)
        xs = [_stream(streams, j, n).astype(f32) for j in range(n)]
        rows = []
        for i in range(n):
            row = h_post[..., i:i + 1] * yf
            for j in range(n):
                k = i * n + j
                row = row + h_res[..., k:k + 1] * xs[j]
            rows.append(row.astype(streams.dtype))
        return jnp.concatenate(rows, axis=-1)

    return jax.checkpoint(stage)(streams, y, h_post, h_res)


def expand(x, *, n):
    """[B, T, C] -> the n streams, each a copy: [B, T, n C]."""
    return jnp.tile(x, n)


def reduce(streams, *, n):
    """The streams' sum: [B, T, n C] -> [B, T, C], in float32 and back."""
    total = sum(_stream(streams, i, n).astype(jnp.float32) for i in range(n))
    return total.astype(streams.dtype)
