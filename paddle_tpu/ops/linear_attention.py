"""Linear-time token mixers' cores and the stages beside them. Two
recurrences and two convolution stages live here; which function is whose:

- **the gated delta rule** (``kda_recurrent`` / ``kda_chunked`` /
  ``gated_delta_rule``, with ``_unit_lower_inverse``, ``_pair_products``,
  ``_scalar_pair_products``, ``_segment``, ``core_path``), under either of
  two decays: ONE PER KEY CHANNEL (Kimi Delta Attention; Kimi Linear
  technical report, arXiv:2510.26692), ``g`` of [B, T, H, d_k], and ONE A
  HEAD (Gated DeltaNet; Yang et al., arXiv:2412.06464 — Qwen3-Next's linear
  layers), ``g`` of [B, T, H];
- **the selective state-space scan** (``ssd_recurrent`` / ``ssd_chunked`` /
  ``ssd_scan``, with ``_ssd_segment``, ``ssd_path``; its Mosaic kernels in
  ``ops/pallas/ssd.py``): Mamba-2's (Dao & Gu,
  arXiv:2405.21060 — Granite-4.0-H's ``mamba`` layers), the scalar-decay
  scan WITHOUT the delta correction, its own section below;
- **the convolution stage before a scan** (``conv_streams`` with
  ``conv_path`` / ``conv_kernel`` / ``_conv_xla`` / ``_conv_kernel``): taps,
  an optional bias, SiLU and q's and k's L2 norm — the three layers' above;
- **the gated short convolution** (``gated_short_conv`` with
  ``shortconv_path`` / ``_gated_xla`` / ``_gated_kernel``): LFM2's whole
  mixer;
- ``head_sums`` / ``on_head_lanes`` / ``head_rsqrt`` / ``l2_normed``: a
  head's statistics on [.., H d] streams, for any of them.

**The gated delta rule.** As a recurrence over tokens and as the chunked
scan: here in XLA operations (what a CPU, a program whose devices are not
known and odd widths run, and what the tests hold everything to), in
``ops/pallas/linear_attention.py`` as the Mosaic kernels a train step on a
TPU runs. Every path takes either decay as it comes: a decay a head is never
broadcast to the channels, and its gradient leaves as [B, T, H].

Per head, with a state S in R^{d_k x d_v} (zero at a row's start), a decay
``g_t <= 0`` (``alpha_t = exp(g_t)``: a vector over the key channels, or a
number), a write strength ``beta_t`` and q, k, v of one token::

    S'_t = Diag(alpha_t) S_{t-1}          (alpha_t S_{t-1} for a decay a head)
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

``kda_recurrent`` is that, one token a ``lax.scan`` step. ``kda_chunked``
computes the same in chunks of C tokens. With ``G_r`` the decay summed from
a chunk's first token to its r-th and ``S_0`` the state entering it::

    A[r, i]  = beta_r <k_r * exp(G_r - G_i), k_i>            (i < r)
    (I + A) U = Diag(beta) (V - (K * exp(G)) S_0)
    o_r      = S_0^T (q_r * exp(G_r)) + sum_{i <= r} <q_r * exp(G_r - G_i), k_i> u_i
    S_C      = Diag(exp(G_C)) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

**Which pair terms each decay computes.** The decay per channel is the
hazard: a pair term does not factor into ``exp(G_r)`` times ``exp(-G_i)``,
because the second overflows where a chunk's decay is strong. Every
exponent there is a difference that is <= 0: the pair terms are computed
exactly (one exponential a pair and channel) on the ``sub`` x ``sub``
diagonal sub-blocks, and against the row sub-block's own first token off
the diagonal (``_pair_products``). With ONE decay a head the scalar leaves
the inner product, ``A[r, i] = beta_r <k_r, k_i> exp(G_r - G_i)``: a
chunk's pair terms are one [C, d] x [d, C] product under a [C, C] mask of
exponentials whose exponents are all <= 0 on the triangle
(``_scalar_pair_products``) — no diagonal sub-blocks, no reference token —
and ``exp(G)``, ``exp(G_C - G)`` and ``exp(G_C)`` are a number a token (a
chunk) that broadcasts over the channels. Everything after the pair terms is
the same code for both. ``T = (I + A)^-1`` is built once a chunk
(``_unit_lower_inverse``), so that only ``U = T beta V - (T beta K exp(G))
S_0`` and the state's update run in the sequential loop over chunks; the
outputs are batched matmuls after it.

Key heads that serve several value heads (Gated DeltaNet's 16 on 32) are the
caller's to repeat: the scan takes one head count (``text.models
.GatedDeltaNet`` does it under a scope of its own).

Precision: G, A, the inverse and the state are float32 whatever q, k and v
are, and A's and the inverse's products ask for float32 in earnest
(``Precision.HIGHEST``: they are a few GFLOP). The [C, d] x [d, d] and
[C, C] x [C, d] products take operands of q's dtype (bf16 under amp O1)
and accumulate in float32.

Memory and time: the sequence is cut into segments of ``segment`` tokens,
an outer scan over them carries the state, and what a backward pass keeps
of one segment (a few arrays of [heads, tokens, d] in float32) is rebuilt
from the segment's inputs (``jax.checkpoint`` on the segment), so the
working set is one segment's and not the row's. On the v5e at 16,384 tokens
(benchmark/tools/kda_candidates.py) a segment of 256 runs forward +
backward in 53 ms where 2,048 takes 121: the transposed inner scan writes
its stacked results a slice at a time, which costs by the stack's size.

``gated_delta_rule`` is the entry point a layer calls: it picks the path
from what it can observe (``core_path``: length, widths, dtype and
``ops.placement``'s answer for the program) and counts the choice in
``paddle_tpu_kda_core_total{path}`` (``kernel`` | ``chunked`` |
``recurrent``, with ``_scalar`` appended for a decay a head). A train step
of the Kimi-Linear or the Qwen3-Next configuration on a TPU takes the
kernels: the same mathematics, a chunk's terms in VMEM, forward and
backward hand-written, nothing of this file's segments; ``chunked`` and
``recurrent`` are this file's.

**One tiling.** The kernels cut their blocks from [B, T, H d] — a head a
lane-aligned slice of the last axis — and on a TPU that is another tiling
than [B, T, H, d] (8 tokens x 128 lanes against 8 heads x 128 lanes): a
reshape between the two is a physical relayout. So the entry point takes q,
k, v (and a per-channel ``g``) in EITHER rank, told apart by ``q.ndim``
with H from beta's [B, T, H], and returns ``o`` in the rank it was given:
streams [B, T, H d] go to the kernels as they are, and a layer that keeps
its element-wise stages on them never makes the head view (``head_sums`` /
``on_head_lanes`` / ``head_rsqrt`` give it the per-head statistics there).
``paddle_tpu_kda_core_entry_total{path, entry}`` counts which entry a call
used: ``streams`` or ``heads`` — ``heads`` on the ``kernel`` path is a
layer that still pays the relayouts. ``chunked`` and ``recurrent`` reshape
streams to heads inside (free where they run).

**The convolution stage** before the scan — each stream's causal depthwise
convolution, its bias where it has one (Mamba-2's) and SiLU, q's and k's L2
norm a head — is ``conv_streams``, by the same rule: ``conv_path`` gives
``kernel`` (one Mosaic call a pass on the streams, its float32 in VMEM;
``ops/pallas/linear_attention.py``) or
``xla`` (this file's ``_conv_xla``: float32 arrays under a
``jax.checkpoint``, what every other program runs and what the kernels are
held to), counted in ``paddle_tpu_conv_streams_total{path}``.

**The gated short convolution** (``gated_short_conv``) is another model's
whole mixer, not a stage before a scan: LFM2's ``C * conv3(B * u)`` between
its two projections, no activation, no norm. By the same rule again:
``shortconv_path`` gives ``kernel`` (one Mosaic call a pass on the bf16
``[B | C | u]`` stream, ``gated_conv_fwd`` / ``_bwd`` of
``ops/pallas/linear_attention.py``: the gates, ``B * u``, the taps'
products and the backward's rebuilt ``conv(B * u)`` float32 in VMEM only,
``bcu`` and the taps all a backward pass keeps, ``d bcu`` one array) or
``xla`` (``_gated_xla``: float32 arrays under a ``jax.checkpoint`` of its
own, as ``_conv_xla``; what every other program runs and what the kernels
are held to), counted in ``paddle_tpu_shortconv_total{path}``. The layer's
call carries no decision — two arrays, no keyword — so the op asks for
itself (``kernel="ask"``).

**The selective state-space scan** (Mamba-2's SSD). Per head, with a state
S in R^{N x P} (zero at a row's start), x_t in R^P, a step ``dt_t > 0``
(after its softplus), ``A < 0`` a head, and B_t, C_t in R^N that belong to
the head's GROUP (``H % G == 0``; Granite-4.0-H has one group for 64
heads)::

    alpha_t = exp(dt_t A)
    S_t = alpha_t S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t + D x_t

It is the delta rule's scalar-decay scan with q = C, k = B, v = dt x and
NOTHING corrected: no ``S'^T k`` is taken off what is written, so there is
no inverse, and ``beta = 0`` in the delta rule is not it (that writes
nothing). ``ssd_recurrent`` is the recurrence, one token a ``lax.scan``
step. ``ssd_chunked`` computes the same in chunks of C tokens, with ``G_r``
the decay's logarithm ``dt A`` summed from a chunk's first token to its
r-th and ``S_0`` the state entering it::

    y_r = sum_{i <= r} <C_r, B_i> exp(G_r - G_i) dt_i x_i
          + exp(G_r) S_0^T C_r + D x_r
    S_C = exp(G_C) S_0 + sum_i exp(G_C - G_i) B_i (dt_i x_i)^T

``<C_r, B_i>`` is the GROUP's: one [C, N] x [N, C] product a chunk and
group, and only the [C, C] mask of exponentials (every exponent <= 0 on the
triangle, as ``_scalar_pair_products``') is a head's. B and C are never
repeated to the heads: the products that meet x carry a group axis and a
heads-in-group axis. The states entering a segment's chunks come from the
chunks' own sums by a [chunks, chunks] triangle of decays a head (no
sequential loop inside a segment: nothing a chunk writes depends on the
state, where the delta rule's ``u`` does); an outer ``lax.scan`` over
segments carries the state, each segment under ``jax.checkpoint``, so a
backward pass holds one segment's float32 (mask, sums, states) and not the
row's. The XLA path's backward is plain autodiff. Precision: ``dt A``, its
sums, the mask, the state and y before its cast are float32 (the sums and
the triangle over chunks ``Precision.HIGHEST``); the [C, N] x [N, C],
[C, C] x [C, P] and [C, N] x [N, P] products take operands of x's dtype
(bf16 under amp O1) and accumulate in float32. ``ssd_scan`` is the entry
point a layer calls: it picks the path from what it can observe
(``ssd_path``: length, widths, groups, dtype and ``ops.placement``'s answer
for the program) and counts the choice in
``paddle_tpu_ssd_core_total{path}`` (``kernel`` | ``chunked`` |
``recurrent``). A train step of the granite-4.0-h-micro configuration on a
TPU takes the kernels (``ops/pallas/ssd.py``: ``ssd_chunk_fwd`` /
``ssd_chunk_bwd`` under one ``jax.custom_vjp``, the backward hand-written,
on the layer's [B, T, H P] and [B, T, G N] streams as the convolution
kernels leave them — the head view and this file's [segments, B, chunks, C,
G, R, P] order never exist in HBM); ``chunked`` and ``recurrent`` are this
file's, what a CPU, a float32 scan, odd widths and a program whose devices
are not known run, and what the tests hold the kernels to.
"""
import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..obs import metrics as obs_metrics
from . import placement

_CORE_TOTAL = obs_metrics.counter(
    "paddle_tpu_kda_core_total",
    "gated-delta-rule cores by the path taken (kernel | chunked | "
    "recurrent, with _scalar appended for one decay a head); under jit one "
    "count per traced layer call",
    labelnames=("path",))

_ENTRY_TOTAL = obs_metrics.counter(
    "paddle_tpu_kda_core_entry_total",
    "gated-delta-rule cores by the path taken and the entry used: streams "
    "([B, T, H d] in and out, the kernels' tiling) | heads ([B, T, H, d], "
    "reshaped by the op: a relayout on the kernel path); under jit one "
    "count per traced layer call",
    labelnames=("path", "entry"))

_CONV_TOTAL = obs_metrics.counter(
    "paddle_tpu_conv_streams_total",
    "linear attention's convolution stages (taps, a bias, SiLU, q's and "
    "k's L2 norm) by the path taken: kernel (one Mosaic call a pass) | "
    "xla; one count per traced layer call",
    labelnames=("path",))

_SHORTCONV_TOTAL = obs_metrics.counter(
    "paddle_tpu_shortconv_total",
    "gated short convolutions (gate, taps, gate between two projections: "
    "LFM2's mixer) by the path taken: kernel (one Mosaic call a pass) | "
    "xla; one count per traced layer call",
    labelnames=("path",))

_SSD_TOTAL = obs_metrics.counter(
    "paddle_tpu_ssd_core_total",
    "selective state-space scans (Mamba-2's SSD: a scalar decay a head, no "
    "delta correction) by the path taken (kernel | chunked | recurrent); "
    "under jit one count per traced layer call",
    labelnames=("path",))

_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens in a diagonal sub-block, whose pair terms are computed exactly
SUB = 16


# ------------------------------------------- per-head statistics on streams
# A layer's float32 statistics over a head's d features (the L2 norms of q
# and k, the output norm's mean square) without the [.., H, d] view: a
# product with the constant 0/1 matrix [H d, H] sums each head's lanes, its
# transpose lays a number a head back on them. Float32 in earnest whatever
# the ambient ``jax.default_matmul_precision``: HIGHEST on the data operand
# (the 0/1 matrix is exact in one bf16 pass).
_DATA_HIGHEST = (_HIGHEST, jax.lax.Precision.DEFAULT)


def _head_lanes(heads, d):
    """[H d, H] float32: 1 where lane ``i`` belongs to head ``h``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads * d, heads), 0)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads * d, heads), 1)
    return (lane // d == head).astype(jnp.float32)


def head_sums(x, heads):
    """The sum over each head's d lanes: float32 [.., H d] -> [.., H]."""
    return jnp.matmul(x, _head_lanes(heads, x.shape[-1] // heads),
                      precision=_DATA_HIGHEST)


def on_head_lanes(s, d):
    """A number a head on each of the head's d lanes: float32 [.., H] ->
    [.., H d]."""
    return jnp.matmul(s, _head_lanes(s.shape[-1], d).T,
                      precision=_DATA_HIGHEST)


def head_rsqrt(x, heads, *, eps, mean):
    """``rsqrt(sum_d x^2 + eps)`` of each head (``mean``: of the sum over
    d), laid on the head's lanes: float32 [.., H d] -> the same shape. An
    L2 norm is ``x * head_rsqrt(x, H, mean=False)``, an RMS norm a head
    ``x * head_rsqrt(x, H, mean=True)``."""
    d = x.shape[-1] // heads
    squares = head_sums(x * x, heads)
    return on_head_lanes(
        jax.lax.rsqrt((squares / d if mean else squares) + eps), d)


def l2_normed(x, heads, *, eps, scale):
    """x [.., H * d] L2-normalised over each head's d features in float32,
    times ``scale``, in x's dtype."""
    xf = x.astype(jnp.float32)
    return (xf * (head_rsqrt(xf, heads, eps=eps, mean=False) * scale)).astype(
        x.dtype)


def kda_recurrent(q, k, v, g, beta, initial_state=None):
    """The recurrence token by token. q, k: [B, T, H, d_k]; v: [B, T, H,
    d_v]; g: [B, T, H, d_k] (a decay per key channel) or [B, T, H] (one a
    head); beta: [B, T, H]. Returns (o [B, T, H, d_v] in v's dtype, the
    final state [B, H, d_k, d_v] in float32). Everything is computed in
    float32."""
    f32 = jnp.float32
    b, _, h, dk = k.shape
    state = (jnp.zeros((b, h, dk, v.shape[-1]), f32) if initial_state is None
             else initial_state.astype(f32))

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = jnp.exp(g_t).reshape(g_t.shape + (1,) * (s.ndim - g_t.ndim)) * s
        u = beta_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HIGHEST))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, sub):
    """X = (I + a)^-1 for strictly lower triangular ``a`` [..., n, n], n a
    power of two times ``sub``. The ``sub`` x ``sub`` diagonal blocks are
    inverted row by row (forward substitution, vector operations); two
    neighbours [[P, 0], [R, Q]] then merge into [[P^-1, 0], [-Q^-1 R P^-1,
    Q^-1]] until one block is left. (A recursion down to single rows is the
    same arithmetic in a hundred small matmuls: it took the TPU compiler
    four minutes a layer.) Its gradient is the inverse's own, d a = -X^T
    (d X) X^T on the strict lower triangle: two products, where
    differentiating the substitution scatters a row at a time."""
    n = a.shape[-1]

    def diagonal(m, row, col):
        """The [m, m] blocks at block (2j + row, 2j + col) of every pair,
        or with row = col = None every diagonal block: [..., blocks, m, m]"""
        if row is None:
            at = [(j, j) for j in range(n // m)]
        else:
            at = [(2 * j + row, 2 * j + col) for j in range(n // (2 * m))]
        return jnp.stack([a[..., r * m:(r + 1) * m, c * m:(c + 1) * m]
                          for r, c in at], axis=-3)

    blocks = diagonal(sub, None, None)
    eye = jnp.eye(sub, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], blocks.shape[:-2] + (sub,))]
    for r in range(1, sub):
        done = jnp.stack(rows, axis=-2)
        rows.append(eye[r] - jnp.sum(blocks[..., r, :r, None] * done,
                                     axis=-2))
    inv, m = jnp.stack(rows, axis=-2), sub
    while m < n:
        p, q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = -jnp.matmul(jnp.matmul(q, diagonal(m, 1, 0),
                                     precision=_HIGHEST), p,
                          precision=_HIGHEST)
        inv = jnp.concatenate(
            [jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
             jnp.concatenate([low, q], axis=-1)], axis=-2)
        m *= 2
    return inv[..., 0, :, :]


def _unit_lower_inverse_fwd(a, sub):
    x = _unit_lower_inverse(a, sub)
    return x, x


def _unit_lower_inverse_bwd(sub, x, g):
    xt = jnp.swapaxes(x, -1, -2)
    grad = -jnp.matmul(jnp.matmul(xt, g, precision=_HIGHEST), xt,
                       precision=_HIGHEST)
    n = x.shape[-1]
    return (jnp.where(jnp.arange(n)[None, :] < jnp.arange(n)[:, None], grad,
                      0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


@jax.custom_vjp
def _diagonal_pairs(qb, kb, gb):
    """The pair terms inside the diagonal sub-blocks, exactly (one
    exponential a (r, i, channel)): qb, kb, gb [..., sub, d] -> ``<k_r *
    exp(G_r - G_i), k_i>`` for i < r and ``<q_r * exp(G_r - G_i), k_i>`` for
    i <= r, zero elsewhere, each [..., sub, sub]."""
    sub = kb.shape[-2]
    r, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    pairs = kb[..., None, :, :] * _sub_block_decay(gb)
    return (jnp.where(i < r, jnp.sum(kb[..., :, None, :] * pairs, axis=-1),
                      0.0),
            jnp.where(i <= r, jnp.sum(qb[..., :, None, :] * pairs, axis=-1),
                      0.0))


def _sub_block_decay(gb):
    """exp(G_r - G_i) [..., r, i, d], the exponent held at 0 above the
    diagonal (where it would be positive and the term is masked)."""
    return jnp.exp(jnp.minimum(gb[..., :, None, :] - gb[..., None, :, :],
                               0.0))


def _diagonal_pairs_fwd(qb, kb, gb):
    return _diagonal_pairs(qb, kb, gb), (qb, kb, gb)


def _diagonal_pairs_bwd(res, cotangents):
    """Three passes over the [r, i, d] terms where differentiating the
    forward takes five: with E = exp(G_r - G_i) and the cotangents masked
    as the outputs are, t_k[r] = sum_i c_k[r, i] k_i E and t_q likewise are
    the gradients of the row's k_r and q_r, t_i[i] = sum_r (c_k k_r + c_q
    q_r) E that of the column's k_i, and the decay's is k_r t_k + q_r t_q at
    the row minus k_i t_i at the column (the r = i term cancels)."""
    qb, kb, gb = res
    sub = kb.shape[-2]
    r, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    c_k = jnp.where(i < r, cotangents[0], 0.0)[..., None]
    c_q = jnp.where(i <= r, cotangents[1], 0.0)[..., None]
    decay = _sub_block_decay(gb)
    pairs = kb[..., None, :, :] * decay
    t_k = jnp.sum(c_k * pairs, axis=-2)
    t_q = jnp.sum(c_q * pairs, axis=-2)
    t_i = jnp.sum((c_k * kb[..., :, None, :] + c_q * qb[..., :, None, :])
                  * decay, axis=-3)
    return t_q, t_k + t_i, kb * (t_k - t_i) + qb * t_q


_diagonal_pairs.defvjp(_diagonal_pairs_fwd, _diagonal_pairs_bwd)


def _pair_products(q, k, cum, sub):
    """The intra-chunk pair terms ``<x_r * exp(G_r - G_i), k_i>`` for x = k
    and x = q: ([..., C, C] for k, zero unless i < r; for q, zero unless
    i <= r). q, k, cum: [..., C, d] in float32, ``cum`` the inclusive sum
    of the decay from the chunk's start. No exponent is positive."""
    *lead, c, d = k.shape
    nb = c // sub

    def blocks(x):
        return x.reshape(*lead, nb, sub, d)

    qb, kb, gb = blocks(q), blocks(k), blocks(cum)
    diag_k, diag_q = _diagonal_pairs(qb, kb, gb)
    rows_k, rows_q = [], []
    for i in range(nb):
        parts_k, parts_q = [diag_k[..., i, :, :]], [diag_q[..., i, :, :]]
        if i:
            # against the row sub-block's first token: rows at or after it
            # have decayed from it, columns before it decay up to it
            ref = gb[..., i, :1, :]
            left = jnp.concatenate([kb[..., i, :, :], qb[..., i, :, :]],
                                   axis=-2) * jnp.tile(
                jnp.exp(gb[..., i, :, :] - ref), (2, 1))
            right = (kb[..., :i, :, :] * jnp.exp(
                ref[..., None, :, :] - gb[..., :i, :, :])).reshape(
                    *lead, i * sub, d)
            off = jnp.einsum("...rd,...id->...ri", left, right,
                             precision=_HIGHEST)
            parts_k.insert(0, off[..., :sub, :])
            parts_q.insert(0, off[..., sub:, :])
        if i < nb - 1:
            zeros = jnp.zeros((*lead, sub, (nb - 1 - i) * sub), k.dtype)
            parts_k.append(zeros)
            parts_q.append(zeros)
        rows_k.append(jnp.concatenate(parts_k, axis=-1))
        rows_q.append(jnp.concatenate(parts_q, axis=-1))
    return (jnp.concatenate(rows_k, axis=-2),
            jnp.concatenate(rows_q, axis=-2))


def _scalar_pair_products(q, k, cum):
    """The pair terms under ONE decay a head: ``<x_r, k_i> exp(G_r - G_i)``
    for x = k (zero unless i < r) and x = q (zero unless i <= r), each
    [..., C, C]: one [C, d] x [d, C] product each under a [C, C] mask of
    exponentials. q, k [..., C, d] and ``cum`` [..., C] in float32. No
    exponent is positive on the triangle; above it the difference is held
    at 0 and the term masked."""
    c = k.shape[-2]
    r, i = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.minimum(cum[..., :, None] - cum[..., None, :], 0.0))
    both = jnp.einsum("...rd,...id->...ri", jnp.concatenate([k, q], axis=-2),
                      k, precision=_HIGHEST)
    return (jnp.where(i < r, both[..., :c, :] * decay, 0.0),
            jnp.where(i <= r, both[..., c:, :] * decay, 0.0))


def _segment(state, xs, *, sub):
    """One segment of whole chunks. xs: q, k [B, H, N, C, d_k] and
    v [B, H, N, C, d_v] in the operand dtype, g [B, H, N, C, d_k] (or
    [B, H, N, C]: a decay a head) and beta [B, H, N, C] in float32; state
    [B, H, d_k, d_v] float32. Returns (the state after the segment,
    o [B, H, N, C, d_v] float32)."""
    q, k, v, g, beta = xs
    f32, mm = jnp.float32, q.dtype
    # the decay summed from the chunk's start, as a product with the lower
    # triangle of ones (a cumsum lowers to a window reduction: 0.26 ms a
    # segment on the v5e against 0.02)
    qf, kf = q.astype(f32), k.astype(f32)
    c = q.shape[-2]
    ones = jnp.tril(jnp.ones((c, c), f32))
    if g.ndim == beta.ndim:
        # a decay a head: the sums are a number a token, the pair terms
        # factor, and everything below broadcasts them over the channels
        cum = jnp.einsum("ri,...i->...r", ones, g, precision=_HIGHEST)
        a_kk, a_qk = _scalar_pair_products(qf, kf, cum)
        cum = cum[..., None]
    else:
        cum = jnp.einsum("ri,...id->...rd", ones, g, precision=_HIGHEST)
        a_kk, a_qk = _pair_products(qf, kf, cum, sub)
    last = cum[..., -1:, :]
    # T = (I + A)^-1 Diag(beta): row r of A carries beta_r, T's columns
    # carry the right-hand side's
    t = (_unit_lower_inverse(beta[..., :, None] * a_kk, sub)
         * beta[..., None, :]).astype(mm)
    w = jnp.einsum("...ri,...id->...rd", t, (kf * jnp.exp(cum)).astype(mm),
                   preferred_element_type=f32).astype(mm)
    u_v = jnp.einsum("...ri,...id->...rd", t, v, preferred_element_type=f32)
    k_out = (kf * jnp.exp(last - cum)).astype(mm)
    decay = jnp.exp(last[..., 0, :])

    def chunk(s, xs):
        w_n, u_n, k_n, decay_n = xs
        u = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s.astype(mm),
                             preferred_element_type=f32)
        new = decay_n[..., None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", k_n, u.astype(mm), preferred_element_type=f32)
        return new, (s, u)

    state, (entering, u) = jax.lax.scan(chunk, state, tuple(
        jnp.moveaxis(x, 2, 0) for x in (w, u_v, k_out, decay)))
    entering, u = jnp.moveaxis(entering, 0, 2), jnp.moveaxis(u, 0, 2)
    o = (jnp.einsum("bhnck,bhnkv->bhncv", (qf * jnp.exp(cum)).astype(mm),
                    entering.astype(mm), preferred_element_type=f32)
         + jnp.einsum("bhnri,bhniv->bhnrv", a_qk.astype(mm), u.astype(mm),
                      preferred_element_type=f32))
    return state, o


def kda_chunked(q, k, v, g, beta, initial_state=None, *, chunk=64,
                segment=256, sub=SUB):
    """The same function as ``kda_recurrent`` (same arguments and results),
    in chunks of ``chunk`` tokens (a power of two, at least ``sub``). Any
    length: the row is padded to whole chunks with tokens that write nothing
    and decay nothing. The large products take q's dtype as their operands'
    (module docstring); the state, and o before its cast to v's dtype, are
    float32."""
    f32 = jnp.float32
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    if chunk & (chunk - 1) or chunk % sub:
        raise ValueError(f"chunk {chunk} must be a power of two and a "
                         f"multiple of {sub}")
    n = -(-t // chunk)
    per_segment = max(1, min(segment // chunk, n))
    segments = -(-n // per_segment)
    pad = segments * per_segment * chunk - t

    def split(x, dtype):
        """[B, T, H, ...] -> [segments, B, H, chunks, C, ...]"""
        x = jnp.pad(x.astype(dtype), [(0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 2))
        x = x.reshape(b, segments, per_segment, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 1), 2, 0)

    xs = (split(q, q.dtype), split(k, q.dtype), split(v, q.dtype),
          split(g, f32), split(beta, f32))
    state = (jnp.zeros((b, h, dk, dv), f32) if initial_state is None
             else initial_state.astype(f32))
    body = functools.partial(_segment, sub=sub)
    if segments == 1:
        state, o = body(state, tuple(x[0] for x in xs))
        o = o[None]
    else:
        state, o = jax.lax.scan(jax.checkpoint(body), state, xs)
    # [segments, B, H, chunks, C, d_v] -> [B, T, H, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 4).reshape(
        b, segments * per_segment * chunk, h, dv)
    return o[:, :t].astype(v.dtype), state


def core_path(seq, d_k=None, d_v=None, dtype=None):
    """``kernel`` | ``chunked`` | ``recurrent`` for a row of ``seq`` tokens
    with keys ``d_k`` and values ``d_v`` wide in ``dtype``, from what can be
    observed: a chunk's set-up (pair terms, an inverse) pays from one
    diagonal sub-block on, else the recurrence; the Mosaic kernels
    (``ops/pallas/linear_attention.py``) where the program may hold them
    (``placement.kernel``: they run through ``on_mesh``), the widths are
    one multiple of the 128 lanes, the operands bf16 or float32 and the row
    at least one chunk; the XLA scan everything else."""
    if seq < SUB:
        return "recurrent"
    from .pallas import linear_attention as kernels

    if (d_k is not None and seq >= kernels.CHUNK
            and kernels.supported(d_k, d_v, dtype)
            and placement.kernel(sharded=True)):
        return "kernel"
    return "chunked"


def gated_delta_rule(q, k, v, g, beta, *, chunk=64):
    """The gated delta rule on Tensors: q, k, v as heads [B, T, H, d]
    (shapes as ``kda_recurrent``) or as streams [B, T, H d] (the kernels'
    tiling; H is beta's), o in the rank given. ``g`` is a decay per key
    channel, shaped like k (Kimi Delta Attention), or one a head, [B, T, H]
    like beta (Gated DeltaNet), and every path takes either as it is: the
    second is never broadcast to the first. The final state stays inside:
    training starts every row from a zero state and keeps none. The
    counter's ``path`` ends in ``_scalar`` for a decay a head."""
    heads = beta.shape[-1]
    streams = q.ndim == 3
    d_k, d_v = ((q.shape[-1] // heads, v.shape[-1] // heads) if streams
                else (q.shape[-1], v.shape[-1]))
    path = core_path(q.shape[1], d_k, d_v, q.dtype)
    counted = path + ("_scalar" if _decay_a_head(g, beta) else "")
    _CORE_TOTAL.inc(path=counted)
    _ENTRY_TOTAL.inc(path=counted, entry="streams" if streams else "heads")
    if path == "recurrent":
        return apply_op("kda_core_recurrent", _recurrent_output, q, k, v, g,
                        beta)
    if path == "kernel":
        # interpret rides the static kwargs so a flag flip retraces
        return apply_op(
            "kda_core_kernel", _kernel_output, q, k, v, g, beta,
            interpret=placement.kernel(sharded=True) == "interpret")
    return apply_op("kda_core", _chunked_output, q, k, v, g, beta,
                    chunk=int(chunk))


def _decay_a_head(g, beta):
    """Whether ``g`` is one decay a head, [B, T, H] like beta, and not one
    per key channel (shaped like k, in either rank)."""
    return g.shape == beta.shape


def _on_heads(scan, q, k, v, g, beta):
    """``scan`` on [B, T, H, d] heads, for arguments of either rank: streams
    [B, T, H d] are viewed as heads and o as a stream again."""
    if q.ndim == 4:
        return scan(q, k, v, g, beta)

    def heads(x):
        return x.reshape(*x.shape[:2], beta.shape[-1], -1)

    o = scan(heads(q), heads(k), heads(v),
             g if _decay_a_head(g, beta) else heads(g), beta)
    return o.reshape(*o.shape[:2], -1)


def _recurrent_output(q, k, v, g, beta):
    return _on_heads(lambda *a: kda_recurrent(*a)[0], q, k, v, g, beta)


def _chunked_output(q, k, v, g, beta, *, chunk):
    return _on_heads(lambda *a: kda_chunked(*a, chunk=chunk)[0], q, k, v, g,
                     beta)


def _kernel_output(q, k, v, g, beta, *, interpret):
    """The Mosaic kernels, under a step's announced mesh inside
    ``placement.on_mesh``'s ``shard_map``: rows over the data axes, heads
    (dim 2 of all five arrays, in either rank: a stream's heads are
    contiguous lane slices) over 'mp', each where it divides."""
    from .pallas import linear_attention as kernels

    def kernel(q, k, v, g, beta):
        return kernels.kda(q, k.astype(q.dtype), v.astype(q.dtype), g, beta,
                           interpret=interpret).astype(v.dtype)

    return placement.on_mesh(kernel, (q, k, v, g, beta), head_axis=2)


# ------------------------------------- the selective state-space scan
#: tokens a chunk and a segment of ``ssd_chunked`` where the caller gives
#: none: a v5e reading at Granite-4.0-H's shape (tools/ssd_bench.py, ms
#: forward / forward + backward, PR 47: 64 x 512 3.98 / 12.15, 128 x 1024
#: 2.54 / 7.64, 256 x 1024 2.36 / 7.10, 256 x 2048 2.23 / 6.86)
SSD_CHUNK, SSD_SEGMENT = 256, 2048


def ssd_recurrent(x, dt, a, b, c, d, initial_state=None):
    """The recurrence token by token. x: [B, T, H, P]; dt: [B, T, H], the
    step after its softplus; a: [H], negative; b, c: [B, T, G, N] with
    ``H % G == 0`` (head h reads group ``h // (H / G)``); d: [H], the
    skip. Returns (y [B, T, H, P] in x's dtype, the final state [B, H, N,
    P] in float32). Everything is computed in float32."""
    f32 = jnp.float32
    bsz, _, h, p = x.shape
    g, n = b.shape[-2:]
    state = (jnp.zeros((bsz, h, n, p), f32) if initial_state is None
             else initial_state.astype(f32))
    af = a.astype(f32)

    def heads(t):
        """a group's [B, G, N] on each of its heads: [B, H, N]"""
        return jnp.repeat(t, h // g, axis=1)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * af)[..., None, None] * s
             + heads(b_t)[..., None] * (dt_t[..., None] * x_t)[..., None, :])
        return s, jnp.einsum("bhnp,bhn->bhp", s, heads(c_t),
                             precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c))
    state, y = jax.lax.scan(step, state, xs)
    y = jnp.moveaxis(y, 0, 1) + d.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype), state


def _ssd_segment(state, xs, *, a, d):
    """One segment of whole chunks. xs: x [B, n, C, G, R, P] (R heads a
    group) and b, c [B, n, C, G, N] in the operand dtype, dt [B, n, C, G, R]
    float32; a, d [G, R] float32; state [B, G, R, N, P] float32. Returns
    (the state after the segment, y [B, n, C, G, R, P] in the operand dtype:
    the float32 sum with the D skip, rounded once, HERE — what leaves a
    segment, and is laid out again as a stream, is not float32)."""
    x, dt, b, c = xs
    f32, mm = jnp.float32, x.dtype
    chunk, chunks = x.shape[2], x.shape[1]
    # the decay's logarithm summed from the chunk's start, as a product with
    # the lower triangle of ones (``_segment``'s reason)
    ones = jnp.tril(jnp.ones((chunk, chunk), f32))
    cum = jnp.einsum("ri,bnigh->bnrgh", ones, dt * a, precision=_HIGHEST)
    last = cum[:, :, -1]                                      # [B, n, G, R]
    # the group's pair terms, and a head's mask of exponentials on them
    pairs = jnp.einsum("bnrgk,bnigk->bngri", c, b, preferred_element_type=f32)
    r, i = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    lanes = jnp.moveaxis(cum, 2, -1)                          # [B, n, G, R, C]
    mask = jnp.where(i <= r, jnp.exp(jnp.minimum(
        lanes[..., :, None] - lanes[..., None, :], 0.0)), 0.0)
    xf = x.astype(f32)
    written = (xf * dt[..., None]).astype(mm)                 # dt x
    y = jnp.einsum("bnghri,bnighp->bnrghp",
                   (pairs[:, :, :, None] * mask).astype(mm), written,
                   preferred_element_type=f32)
    # what each chunk adds to the state by its end
    sums = jnp.einsum(
        "bnigk,bnighp->bnghkp", b,
        (xf * (dt * jnp.exp(last[:, :, None] - cum))[..., None]).astype(mm),
        preferred_element_type=f32)
    # the state entering chunk m (m = chunks: leaving the segment) from the
    # entering state and the chunks before it: exponents of whole chunks'
    # decays, every one <= 0
    total = jnp.cumsum(last, axis=1)                          # [B, n, G, R]
    upto = jnp.concatenate([jnp.zeros_like(total[:, :1]), total], axis=1)
    m, j = jnp.arange(chunks + 1)[:, None], jnp.arange(chunks)[None, :]
    upto_l, total_l = jnp.moveaxis(upto, 1, -1), jnp.moveaxis(total, 1, -1)
    carry = jnp.where(j < m, jnp.exp(jnp.minimum(
        upto_l[..., :, None] - total_l[..., None, :], 0.0)), 0.0)
    states = (jnp.exp(upto_l)[..., None, None] * state[..., None, :, :]
              + jnp.einsum("bghmj,bjghkp->bghmkp", carry, sums,
                           precision=_HIGHEST))    # [B, G, R, n + 1, N, P]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bnrgk,bghnkp->bnrghp", c, states[:, :, :, :-1].astype(mm),
        preferred_element_type=f32)
    return states[:, :, :, -1], (y + d[:, :, None] * xf).astype(mm)


def ssd_chunked(x, dt, a, b, c, d, initial_state=None, *,
                chunk=SSD_CHUNK, segment=SSD_SEGMENT):
    """The same function as ``ssd_recurrent`` (same arguments and results),
    in chunks of ``chunk`` tokens and segments of ``segment`` (whole
    chunks). Any length: the row is padded to whole segments with tokens of
    step 0, which write nothing and decay nothing. The large products take
    x's dtype as their operands' (module docstring); the state, and y until
    its one rounding to x's dtype — the D skip added, at a segment's end —
    are float32."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    if h % g:
        raise ValueError(f"{h} heads are no multiple of {g} groups")
    chunk = min(int(chunk), max(1, t))
    chunks = -(-t // chunk)
    per_segment = max(1, min(int(segment) // chunk, chunks))
    segments = -(-chunks // per_segment)
    pad = segments * per_segment * chunk - t

    def split(v, dtype, *tail):
        """[B, T, ...] -> [segments, B, chunks, C, *tail]"""
        v = jnp.pad(v.astype(dtype), [(0, 0), (0, pad)] + [(0, 0)] * (
            v.ndim - 2))
        return jnp.moveaxis(
            v.reshape(bsz, segments, per_segment, chunk, *tail), 1, 0)

    xs = (split(x, x.dtype, g, h // g, p), split(dt, f32, g, h // g),
          split(b, x.dtype, g, n), split(c, x.dtype, g, n))
    state = (jnp.zeros((bsz, h, n, p), f32) if initial_state is None
             else initial_state.astype(f32)).reshape(bsz, g, h // g, n, p)
    body = functools.partial(_ssd_segment,
                             a=a.astype(f32).reshape(g, h // g),
                             d=d.astype(f32).reshape(g, h // g))
    if segments == 1:
        state, y = body(state, tuple(v[0] for v in xs))
        y = y[None]
    else:
        state, y = jax.lax.scan(jax.checkpoint(body), state, xs)
    # [segments, B, chunks, C, G, R, P] -> [B, T, H, P]
    return (jnp.moveaxis(y, 0, 1).reshape(bsz, -1, h, p)[:, :t],
            state.reshape(bsz, h, n, p))


def ssd_path(seq, heads=None, groups=1, d_head=None, d_state=None,
             dtype=None):
    """``kernel`` | ``chunked`` | ``recurrent`` for a row of ``seq`` tokens
    of ``heads`` heads of ``d_head`` in ``groups`` groups on a state of
    ``d_state``, operands in ``dtype``, from what can be observed, as
    ``core_path``: a chunk's set-up (the pair product, the mask) pays from
    ``SUB`` tokens on, else the recurrence; the Mosaic kernels
    (``ops/pallas/ssd.py``) where the program may hold them
    (``placement.kernel``: they run through ``on_mesh``), the state fills
    whole lane groups, the values' width divides or is a multiple of the 128
    lanes, a head cut serves, the operands are bf16 (a float32 scan — the
    cell's float32 check — stays the XLA path's) and the row is at least one
    token block; the XLA scan everything else: every CPU run, every toy
    width, ``use_pallas_kernels`` off."""
    if seq < SUB:
        return "recurrent"
    from .pallas import ssd as kernels

    if (heads is not None and seq >= kernels.TOKENS
            and kernels.supported(heads, groups, d_head, d_state, dtype)
            and placement.kernel(sharded=True)):
        return "kernel"
    return "chunked"


def ssd_scan(x, dt, a, b, c, d, *, groups=1, chunk=SSD_CHUNK,
             segment=SSD_SEGMENT):
    """The selective state-space scan on Tensors: x as heads [B, T, H, P]
    or as a stream [B, T, H P] (H is dt's), y in the rank given; dt [B, T,
    H] after its softplus, a [H], b and c [B, T, G, N] or as streams [B, T,
    G N] of ``groups`` groups, d [H]. The final state stays inside:
    training starts every row from a zero state and keeps none. The path
    is ``ssd_path``'s, taken here, outside the dispatched op (``interpret``
    rides its static arguments, so a flag flipped or a mesh announced
    retraces), and counted in ``paddle_tpu_ssd_core_total{path}``, one
    count a traced call. ``chunk`` and ``segment`` are the XLA path's: the
    kernels choose their own (``ops/pallas/ssd.py``'s constants)."""
    heads = dt.shape[-1]
    groups = int(groups) if b.ndim == 3 else b.shape[2]
    path = ssd_path(
        x.shape[1], heads, groups,
        x.shape[-1] // (heads if x.ndim == 3 else 1),
        b.shape[-1] // (groups if b.ndim == 3 else 1), x.dtype)
    _SSD_TOTAL.inc(path=path)
    if path == "recurrent":
        return apply_op("ssd_core_recurrent", _ssd_recurrent_output, x, dt,
                        a, b, c, d, groups=groups)
    if path == "kernel":
        return apply_op(
            "ssd_core_kernel", _ssd_kernel_output, x, dt, a, b, c, d,
            groups=groups,
            interpret=placement.kernel(sharded=True) == "interpret")
    return apply_op("ssd_core", _ssd_chunked_output, x, dt, a, b, c, d,
                    groups=groups, chunk=int(chunk), segment=int(segment))


def _ssd_on_heads(scan, x, dt, a, b, c, d, groups):
    """``scan``'s y for x, b and c of either rank: streams are viewed as
    heads (groups) and y as x came."""
    if b.ndim == 3:
        b, c = (t.reshape(*t.shape[:2], groups, -1) for t in (b, c))
    heads = x.reshape(*x.shape[:2], dt.shape[-1], -1)
    return scan(heads, dt, a, b, c, d)[0].reshape(x.shape)


def _ssd_recurrent_output(x, dt, a, b, c, d, *, groups):
    return _ssd_on_heads(ssd_recurrent, x, dt, a, b, c, d, groups)


def _ssd_chunked_output(x, dt, a, b, c, d, *, groups, chunk, segment):
    return _ssd_on_heads(functools.partial(
        ssd_chunked, chunk=chunk, segment=segment), x, dt, a, b, c, d,
        groups)


def _ssd_kernel_output(x, dt, a, b, c, d, *, groups, interpret):
    """The Mosaic kernels on streams (heads [B, T, H, P] are viewed as
    streams and y as x came: on a TPU a relayout, a layer that can hands
    over streams), under a step's announced mesh inside
    ``placement.on_mesh``'s ``shard_map``: rows over the data axes, THE
    HEADS WHOLE on every device of an 'mp' axis — B and C are one group's
    for all its heads, which ``on_mesh``'s single ``head_axis`` cannot
    express. ``on_mesh`` shards dim 0 of every array, so the per-head A and
    D go a copy a row ([B, H]) and their gradients are summed over the rows
    outside."""
    from .pallas import ssd as kernels

    def kernel(x, dt, a, b, c, d):
        return kernels.ssd(x, dt, a, b, c, d, groups=groups,
                           interpret=interpret)

    def stream(t):
        return t.reshape(*t.shape[:2], -1)

    def rows(t):
        return jnp.broadcast_to(t.astype(jnp.float32)[None],
                                (x.shape[0],) + t.shape)

    y = placement.on_mesh(
        kernel, (stream(x), dt, rows(a), stream(b), stream(c), rows(d)),
        head_axis=None)
    return y.reshape(x.shape)


# ------------------------------------------------- the convolution stage
def conv_path(seq, segments, head, taps, dtype):
    """``kernel`` | ``xla`` for the convolution stage of a row of ``seq``
    tokens whose ``segments`` (``(stream, start, width, scale)``, as
    ``conv_streams`` takes them) have heads ``head`` wide and ``taps`` taps
    in ``dtype``, from what can be observed: the Mosaic kernels where the
    program may hold them (``placement.kernel``: they run through
    ``on_mesh``), every segment's channels fill whole lane groups — a
    normed segment's heads too —, the history fits the rows the kernels
    carry, the streams are bf16 or float32 and the row is at least one
    token block; where ``on_mesh`` cuts the heads over an 'mp' axis, only
    if that cuts every stream between whole heads of ONE segment. The XLA
    stage everything else. A bias changes nothing of it: the kernels take
    one beside the taps."""
    from .pallas import linear_attention as kernels

    if not (seq >= kernels.CONV_TOKENS
            and kernels.conv_supported(segments, head, taps, dtype)
            and placement.kernel(sharded=True)):
        return "xla"
    mp = placement.axis_size("mp")
    streams = [stream for stream, *_ in segments]
    if mp > 1 and not (len(set(streams)) == len(streams) and all(
            width % (mp * head) == 0 for _, _, width, _ in segments)):
        return "xla"
    return "kernel"


def conv_kernel(x, w, segments, head):
    """One call's decision, counted, as ``conv_streams`` takes it:
    ``placement.kernel``'s answer where ``conv_path`` says ``kernel`` for
    streams like ``x`` and taps like ``w`` (with a bias or without), else
    None (the XLA stage). An op's caller asks OUTSIDE the op; the answer
    rides its static arguments."""
    path = conv_path(x.shape[1], segments, head, w.shape[0], x.dtype)
    _CONV_TOTAL.inc(path=path)
    return placement.kernel(sharded=True) if path == "kernel" else None


def conv_streams(xs, ws, segments, *, head, eps, kernel="ask", biases=None):
    """Linear attention's stage between the projections and the scan, on
    arrays: streams ``xs`` [B, T, C_i] with their taps ``ws`` [K, C_i] ->
    one [B, T, width] array a segment ``(stream, start, width, scale)``:
    ``width`` channels of ``xs[stream]`` from ``start`` on through the
    causal depthwise convolution (float32 multiply-adds, zero history) and
    SiLU, then, where ``scale`` is a number, L2-normalised over each
    ``head`` features in float32 and scaled; where it is None, as SiLU left
    them. The segments cover every stream in order. Results take their
    stream's dtype. ``biases``: None, or one [C_i] array a stream, added to
    the taps' sum before SiLU (the state-space layer's stage: x | B | C are
    three ``scale=None`` segments of one biased stream), on either path.
    ``kernel``: ``conv_kernel``'s answer, from a caller under ``apply_op``;
    ``"ask"`` (under the caller's own jit): taken here."""
    segments = tuple((int(s), int(a), int(n), None if c is None else float(c))
                     for s, a, n, c in segments)
    if kernel == "ask":
        kernel = conv_kernel(xs[0], ws[0], segments, head)
    if kernel is None:
        return _conv_xla(tuple(xs), tuple(ws), segments, head, eps, biases)
    return _conv_kernel(tuple(xs), tuple(ws), segments, head, eps,
                        kernel == "interpret", biases)


def _conv_xla(xs, ws, segments, head, eps, biases=None):
    """The stage in XLA operations: float32 arrays as large as a stream,
    so a ``jax.checkpoint`` of its own — a differentiated program keeps
    the (bf16) streams and rebuilds the float32 inside it."""
    from ..nn import functional as F

    def stage(xs, ws, *biases):
        made = [F._causal_depthwise_conv1d(x, w, *bias, activation="silu")
                for x, w, *bias in zip(xs, ws, *biases)]
        outs = []
        for stream, start, width, scale in segments:
            y = made[stream][..., start:start + width]
            outs.append(y if scale is None else l2_normed(
                y, width // head, eps=eps, scale=scale))
        return tuple(outs)

    return jax.checkpoint(stage)(
        xs, ws, *(() if biases is None else (tuple(biases),)))


def _conv_kernel(xs, ws, segments, head, eps, interpret, biases=None):
    """The Mosaic kernels, under a step's announced mesh inside
    ``placement.on_mesh``'s ``shard_map``: rows over the data axes and,
    where ``conv_path`` lets an ``mp`` axis through, every stream's heads
    over it. The taps go a copy a row ([B, K, C]), so that they shard as
    the streams do and their gradient is summed over the rows outside; a
    stream's bias goes with them, as the copy's row K."""
    from .pallas import linear_attention as kernels

    n, batch = len(xs), xs[0].shape[0]
    bias = biases is not None

    def kernel(*arrays):
        xs, ws = arrays[:n], arrays[n:]
        # a stream cut over 'mp' is one segment: what this shard holds of it
        local = tuple((s, start, min(width, xs[s].shape[-1]), scale)
                      for s, start, width, scale in segments)
        return kernels.conv_streams(xs, ws, local, head=head, eps=eps,
                                    bias=bias, interpret=interpret)

    if bias:
        ws = tuple(jnp.concatenate([w, b[None]]) for w, b in zip(ws, biases))
    rows = tuple(jnp.broadcast_to(w[None], (batch,) + w.shape) for w in ws)
    return tuple(placement.on_mesh(kernel, (*xs, *rows), head_axis=2))


# ------------------------------------------- the gated short convolution
def shortconv_path(seq, channels, taps, dtype):
    """``kernel`` | ``xla`` for the gated short convolution of a row of
    ``seq`` tokens, ``channels`` channels a third and ``taps`` taps in
    ``dtype``, counted, from what can be observed, as ``conv_path``: the
    Mosaic kernels where the program may hold them (``placement.kernel``:
    they run through ``on_mesh``), the channels fill whole lane groups, the
    history fits the rows the kernels carry, the stream is bf16 or float32
    and the row is at least one token block. The XLA stage everything
    else."""
    from .pallas import linear_attention as kernels

    path = ("kernel" if kernels.gated_conv_supported(channels, taps, dtype)
            and seq >= kernels.gated_conv_tokens(channels, dtype)
            and placement.kernel(sharded=True) else "xla")
    _SHORTCONV_TOTAL.inc(path=path)
    return path


def gated_short_conv(bcu, w, kernel="ask"):
    """LFM2's stage between its two projections, on arrays: ``bcu`` [B, T,
    3 C] as ``in_proj`` leaves it, split [B | C | u] in that order, and the
    taps ``w`` [K, C] -> ``C * conv(B * u)`` [B, T, C] in bcu's dtype. The
    convolution is causal and depthwise (``F.causal_depthwise_conv1d``: tap
    K - 1 meets the token itself, zero history before a row's first
    token — a row never sees another row), no bias, no activation; gates,
    products and sums are float32 on either path. ``kernel``:
    ``placement.kernel``'s answer or None (the XLA stage); ``"ask"``, what
    the layer's two-argument call leaves it at: ``shortconv_path`` is asked
    here, once a call (an eager op's cached trace keeps its first answer:
    ``dispatch.evict_ops("gated_short_conv")`` after a flag's flip)."""
    if kernel == "ask":
        kernel = (placement.kernel(sharded=True) if shortconv_path(
            bcu.shape[1], w.shape[1], w.shape[0], bcu.dtype) == "kernel"
            else None)
    if kernel is None:
        return _gated_xla(bcu, w)
    return _gated_kernel(bcu, w, kernel == "interpret")


def _gated_xla(bcu, w):
    """The stage in XLA operations: gates and taps are float32 arrays as
    large as a stream, so a ``jax.checkpoint`` of its own, as ``_conv_xla``:
    a differentiated program keeps ``bcu`` and rebuilds the float32 inside
    it."""
    from ..nn import functional as F

    channels = w.shape[1]

    def stage(bcu, w):
        b, c, u = (bcu[..., i * channels:(i + 1) * channels]
                   .astype(jnp.float32) for i in range(3))
        mixed = F._causal_depthwise_conv1d(b * u, w, activation=None)
        return (c * mixed).astype(bcu.dtype)

    return jax.checkpoint(stage)(bcu, w)


def _gated_kernel(bcu, w, interpret):
    """The Mosaic kernels (``gated_conv_fwd`` / ``_bwd``: ``bcu`` and the
    taps are all a backward pass keeps, no checkpoint), under a step's
    announced mesh inside ``placement.on_mesh``'s ``shard_map``: rows over
    the data axes, whole on every device of an 'mp' axis (a cut of 3 C
    would not cut B, C and u alike). The taps go a copy a row ([B, K, C]),
    so that they shard as the stream does and their gradient is summed over
    the rows outside."""
    from .pallas import linear_attention as kernels

    def kernel(bcu, rows):
        return kernels.gated_conv(bcu, rows, interpret=interpret)

    rows = jnp.broadcast_to(w[None], (bcu.shape[0],) + w.shape)
    return placement.on_mesh(kernel, (bcu, rows), head_axis=None)
