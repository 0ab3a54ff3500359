"""The gated delta rule's chunked scan (``ops/linear_attention.py``, whose
docstring has the equations) as two Pallas TPU kernels under a
``jax.custom_vjp``: ``kda_chunk_fwd`` and ``kda_chunk_bwd``, for a decay per
key channel (Kimi Delta Attention) and for one a head (Gated DeltaNet).

A program is one batch row, ``TOGETHER`` heads and one block of ``TOKENS``
tokens, taken from the layer's arrays viewed as [batch, tokens, heads x d]
(block ``(1, tokens, G d)`` at ``(b, n, h)``, a head a lane-aligned slice
of it: the scan's [heads, chunks] order never exists in HBM). The block
axis is the grid's last, ``arbitrary``: the heads' states [d_k, d_v] live
in float32 VMEM scratch across it. A block's set-up — everything that does
not wait for the state — is computed for all its chunks and heads at once,
as batched products; only two products a chunk wait for one another, the
program's heads side by side, and the transposes they would need are made
in the set-up.

A chunk, in VMEM and registers only:

- ``G`` = the decay summed from the chunk's first token (a product with
  the triangle of ones, float32 in earnest: three bf16 passes over the
  decay split exactly in three);
- the pair terms (a decay per key channel; for one a head see below) ``<x_r * exp(G_r - G_i), k_i>`` for x = k (i < r) and
  x = q (i <= r): on the ``SUB`` x ``SUB`` diagonal sub-blocks exactly, one
  exponential a (r, i, channel), a column of every sub-block at a time;
  off the diagonal against the row sub-block's first token, as a product.
  No exponent is positive anywhere: a difference that could be is held at
  0 where its term is masked. A column of a sub-block's later half meets
  the rows of that half only, so those columns run on half the rows;
- ``X = (I + diag(beta) A)^-1``: diagonal blocks of ``INVERSE_SUB`` rows by
  forward substitution (rank-one updates on their columns), then block
  merges ``[[P, 0], [-Q R P, Q]]`` up to the chunk, float32 in earnest;
- ``T = X diag(beta)``, ``W = T (K e^G)``, ``U = T V - W S``, the state's
  update and ``o = (q e^G) S + A_qk U``: operands of q's dtype, float32
  accumulation.

The forward under differentiation also writes what the backward takes
instead of rebuilding it: each chunk's entering state [d_k, d_v] and its
three [C, C] matrices (A for k, A for q, X), all float32 — 112 KB a chunk
and head, alive from a block's recomputed forward to its backward. The
backward walks the blocks and their chunks in reverse with ``dS`` in VMEM
scratch, recomputes G, the decays, W and U from them, and emits dq, dk, dv
(q's dtype), dg and dbeta (float32): the inverse's gradient is ``-X^T dX
X^T`` on the strict lower triangle, the diagonal pair terms' is three
sums over the same [r, i, d] terms the forward made (``ops/
linear_attention._diagonal_pairs_bwd``), a column at a time again.

**One decay a head** (Gated DeltaNet: ``g`` of [B, T, H]). The kernels take
it as it is, a row a chunk [B, H, T / C, 1, C] like beta — no [.., d_k]
broadcast in HBM, forward or backward, and ``dg`` leaves in the same shape.
In VMEM: ``G`` is a masked lane sum on the vector unit, a column [C, 1] a
chunk; the pair terms FACTOR, ``<x_r, k_i> exp(G_r - G_i)``: one
[C, d] x [d, C] product for k and one for q (bf16 operands multiply exactly
into the float32 sum) under a [C, C] mask of exponentials, every exponent
<= 0 on the triangle — none of the diagonal sub-blocks' column-at-a-time
vector work, which is where the per-channel forward spends a seventh of its
time (PERF.md section 5); ``exp(G)``, ``exp(G_C - G)`` are columns that
broadcast over the lanes, the chunk's decay a number. The backward's pair
terms are four products and the decay's gradient a row sum minus a column
sum of (cotangent x term). Everything else — the inverse, T, W, U, the
loop over chunks, what is kept — is the per-channel code, whose program is
unchanged (the branch is on the decay's shape at trace time).

``interpret=True`` runs both in the Pallas interpreter (the CPU tests and
the chip_smoke dry run ask for it; never inferred from the backend).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
#: tokens in a diagonal sub-block (``ops.linear_attention.SUB``)
SUB = 16
#: rows of the diagonal blocks the inverse takes by substitution (vector
#: work, a step a row); the block merges above them are products
INVERSE_SUB = 8
#: tokens a program takes: its set-up is batched over their chunks, so more
#: of them is more code and more to overlap
TOKENS = 256
#: heads a program takes, side by side in the lanes of its blocks: their
#: states' products, which wait for one another along a row, interleave
TOGETHER = 4

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
_F32 = jnp.float32

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def supported(d_k, d_v, dtype):
    """Whether the kernels take these widths and this operand dtype: one
    width for keys and values that fills whole lane groups."""
    return (d_k == d_v and d_k % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _precision(a, precision):
    """bf16 operands multiply as they are, whatever precision the caller's
    context (``jax.default_matmul_precision``) asks of float32 ones."""
    return jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else precision


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=_precision(a, precision),
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _grid_masks():
    """[C, C] index planes: row, column."""
    return _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)


def _eye(n):
    return _iota((n, n), 0) == _iota((n, n), 1)


def _local(rows):
    """[C * rows / SUB, C]: for the ``rows`` last rows of every diagonal
    sub-block, the column's place in the row's own sub-block."""
    shape = (CHUNK * rows // SUB, CHUNK)
    return _iota(shape, 1) - (_iota(shape, 0) // rows) * SUB


def _bmm(a, b, dims, precision=None):
    """A product a chunk: [N, ., .] x [N, ., .], ``dims`` the contracted
    axes of one chunk's pair (``_NN`` | ``_NT`` | ``_TN``)."""
    (ca,), (cb,) = dims[0]
    return jax.lax.dot_general(
        a, b, (((ca + 1,), (cb + 1,)), ((0,), (0,))),
        precision=_precision(a, precision), preferred_element_type=_F32)


def _own(x, j, sub=SUB):
    """Row j of every sub-block of ``sub`` rows, for each of its rows:
    [..., n] -> the same shape."""
    x3 = x.reshape(-1, sub, x.shape[-1])
    return jnp.broadcast_to(x3[:, j:j + 1, :], x3.shape).reshape(x.shape)


def _lower_rows(x):
    """The later half of every diagonal sub-block's rows: [N, C, n] -> [N,
    C / 2, n] (whole 8-row tiles)."""
    x3 = x.reshape(-1, SUB, x.shape[-1])[:, SUB // 2:]
    return x3.reshape(x.shape[0], CHUNK // 2, x.shape[-1])


def _with_lower_rows(x, lower):
    """``x`` [N, C, n] with those rows replaced."""
    x3 = x.reshape(-1, SUB, x.shape[-1])
    return jnp.concatenate(
        [x3[:, :SUB // 2], lower.reshape(-1, SUB // 2, x.shape[-1])],
        axis=1).reshape(x.shape)


def _in_three(x):
    """x in float32 as three bf16 terms that sum to it exactly."""
    terms, rest = [], x
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(_F32)
    return terms


def _ones_product(ones, x):
    """ones [C, C] (entries 0 or 1) times x [N, C, d], float32 in earnest
    in three bf16 passes instead of a float32 product's six: a 0 or 1
    multiplies each of x's three bf16 terms exactly, and the sums are
    float32."""
    ones = jnp.broadcast_to(ones.astype(jnp.bfloat16),
                            (x.shape[0], CHUNK, CHUNK))
    return sum(_bmm(ones, term, _NN) for term in _in_three(x))


def _picked(x, ones, dims):
    """x [rows, .] times a matrix of 0 and 1 [., .], the same way."""
    ones = ones.astype(jnp.bfloat16)
    return sum(_dot(term, ones, dims) for term in _in_three(x))


def _decay_sums(g):
    """G [N, C, d]: g summed from each chunk's first token, inclusive."""
    r, i = _grid_masks()
    return _ones_product(i <= r, g)


def _pair_terms(qf, kf, cum):
    """(A_kk zero unless i < r, A_qk zero unless i <= r), each [N, C, C]
    float32, from float32 q, k and the decay sums [N, C, d]."""
    r, i = _grid_masks()
    n = kf.shape[0]
    a_kk = jnp.zeros((n, CHUNK, CHUNK), _F32)
    a_qk = jnp.zeros((n, CHUNK, CHUNK), _F32)

    def column(j, qf, kf, cum, rows, a_kk, a_qk):
        """Column j of every diagonal sub-block into the two stacks, for
        the sub-blocks' ``rows`` given (all SUB, or their later half: the
        column's own row stands at ``j - (SUB - rows)`` among them). Rows
        before the column are masked at the end, their exponent held at 0
        here."""
        at_row = j - (SUB - rows)
        pairs = _own(kf, at_row, rows) * jnp.exp(
            jnp.minimum(cum - _own(cum, at_row, rows), 0.0))
        at = _local(rows) == j
        return (jnp.where(at, jnp.sum(kf * pairs, axis=-1, keepdims=True),
                          a_kk),
                jnp.where(at, jnp.sum(qf * pairs, axis=-1, keepdims=True),
                          a_qk))

    for j in range(SUB // 2):
        a_kk, a_qk = column(j, qf, kf, cum, SUB, a_kk, a_qk)
    # a column of a sub-block's later half meets rows of that half only
    lower = tuple(map(_lower_rows, (qf, kf, cum, a_kk, a_qk)))
    low_kk, low_qk = lower[3:]
    for j in range(SUB // 2, SUB):
        low_kk, low_qk = column(j, *lower[:3], SUB // 2, low_kk, low_qk)
    a_kk = _with_lower_rows(a_kk, low_kk)
    a_qk = _with_lower_rows(a_qk, low_qk)
    rows_kk, rows_qk = [a_kk[:, :SUB]], [a_qk[:, :SUB]]
    for s in range(1, CHUNK // SUB):
        rows = slice(s * SUB, (s + 1) * SUB)
        left, right, _, _ = _against_first(qf, kf, cum, s)
        off = _bmm(left, right, _NT, _HIGHEST)               # [N, 2 SUB, C]
        before = _iota((SUB, CHUNK), 1) < s * SUB
        rows_kk.append(jnp.where(before, off[:, :SUB], a_kk[:, rows]))
        rows_qk.append(jnp.where(before, off[:, SUB:], a_qk[:, rows]))
    a_kk = jnp.concatenate(rows_kk, axis=1)
    a_qk = jnp.concatenate(rows_qk, axis=1)
    return jnp.where(i < r, a_kk, 0.0), jnp.where(i <= r, a_qk, 0.0)


def _against_first(qf, kf, cum, s):
    """Row sub-block s against its own first token: (k and q of its rows,
    decayed from that token on, [N, 2 SUB, d]; every k decayed up to it —
    held where it comes after — [N, C, d]; and the two decays)."""
    rows = slice(s * SUB, (s + 1) * SUB)
    ref = cum[:, s * SUB:s * SUB + 1]
    grown = jnp.exp(cum[:, rows] - ref)
    shrunk = jnp.exp(jnp.minimum(ref - cum, 0.0))
    left = jnp.concatenate([kf[:, rows] * grown, qf[:, rows] * grown], axis=1)
    return left, kf * shrunk, grown, shrunk


def _fold(sub):
    """[C, sub]: picks, a row, the ``sub`` columns of its own diagonal
    block of that size."""
    return _iota((CHUNK, sub), 0) % sub == _iota((CHUNK, sub), 1)


def _same_block(sub):
    """[C, C]: whether a column lies in the row's own diagonal block."""
    r, i = _grid_masks()
    return r // sub == i // sub


def _own_columns(a, sub=SUB):
    """[N, C, C] -> [N C, sub]: each row's entries in its own diagonal
    block of ``sub`` columns."""
    return _picked(jnp.where(_same_block(sub), a, 0.0).reshape(-1, CHUNK),
                   _fold(sub), _NN)


def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower triangular n [N, C, C], float32."""
    r, i = _grid_masks()
    chunks, sub = n.shape[0], INVERSE_SUB
    # the diagonal blocks of ``sub`` rows, ``sub`` columns wide: x starts
    # as the identity; once row j of a block is final, every later row r of
    # it takes -n[r, j] times that row
    nd = _own_columns(n, sub)                                # [N C, sub]
    x = (_iota(nd.shape, 1) == _iota(nd.shape, 0) % sub).astype(_F32)
    for j in range(sub - 1):
        x = x - nd[:, j:j + 1] * _own(x, j, sub)
    x = _picked(x, _fold(sub), _NT).reshape(chunks, CHUNK, CHUNK)
    x = jnp.where(_same_block(sub), x, 0.0)                  # block diagonal
    # the merges: with R the blocks under the diagonal at this level,
    # [[P, 0], [R, Q]]^-1 = X - X R X for the block-diagonal X so far; only
    # the rows of the lower blocks move
    m = sub
    while m < CHUNK:
        under = _same_block(2 * m) & ~_same_block(m)
        pieces = [x[:, b * m:(b + 1) * m] for b in range(CHUNK // m)]
        lower = jnp.concatenate(pieces[1::2], axis=1)        # [N, C / 2, C]
        low = _bmm(_bmm(lower, jnp.where(under, n, 0.0), _NN, _HIGHEST), x,
                   _NN, _HIGHEST)
        for b in range(1, CHUNK // m, 2):
            pieces[b] = pieces[b] - low[:, (b // 2) * m:(b // 2 + 1) * m]
        x = jnp.concatenate(pieces, axis=1)
        m *= 2
    return x


def _as_row(x):
    """[N, C, 1] -> [N, 1, C] without a transpose: the diagonal's column
    sums."""
    return jnp.sum(jnp.where(_eye(CHUNK), x, 0.0), axis=1, keepdims=True)


def _as_column(x):
    """[N, 1, C] -> [N, C, 1], the diagonal's row sums."""
    return jnp.sum(jnp.where(_eye(CHUNK), x, 0.0), axis=2, keepdims=True)


def _scalar_decay_sums(g):
    """G [N, C, 1] from a decay a head g [N, 1, C] (a row a chunk, as beta
    comes): each token's sum from its chunk's first token, inclusive, in
    float32 on the vector unit (a masked lane sum; no product to round)."""
    r, i = _grid_masks()
    return jnp.sum(jnp.where(i <= r, g, 0.0), axis=2, keepdims=True)


def _scalar_decay_mask(cum):
    """exp(G_r - G_i) [N, C, C] from the sums as a column [N, C, 1]; above
    the diagonal, where the difference is positive and the term is masked,
    it is held at 0."""
    return jnp.exp(jnp.minimum(cum - _as_row(cum), 0.0))


def _scalar_pair_terms(q, k, cum):
    """The pair terms under ONE decay a head: they factor, ``<x_r, k_i>
    exp(G_r - G_i)``, so a chunk's are one [C, d] x [d, C] product for k
    and one for q under a [C, C] mask of exponentials — no diagonal
    sub-blocks, no reference token. q, k [N, C, d] in the operand dtype
    (bf16 products of bf16 numbers are exact in the float32 sum; float32
    operands ask for float32 in earnest), ``cum`` [N, C, 1]."""
    r, i = _grid_masks()
    decay = _scalar_decay_mask(cum)
    return (jnp.where(i < r, _bmm(k, k, _NT, _HIGHEST) * decay, 0.0),
            jnp.where(i <= r, _bmm(q, k, _NT, _HIGHEST) * decay, 0.0))


def _set_up(q, k, v, g, beta, mats=None):
    """What a block's chunks need that does not wait for the state. q, k,
    v [N, C, d] (the operand dtype), beta [N, 1, C] float32, g float32
    [N, C, d] (a decay per key channel) or [N, 1, C] (one a head, a row a
    chunk like beta: its sums, ``grown``, ``shrunk`` and ``decay`` are then
    [N, C, 1] and [N, 1, 1] and broadcast over the channels); ``mats`` =
    (A_kk, A_qk, X) where the caller kept them. A dict of [N, ...]
    stacks."""
    mm = q.dtype
    qf, kf = q.astype(_F32), k.astype(_F32)
    scalar = g.shape[1] == 1
    cum = _scalar_decay_sums(g) if scalar else _decay_sums(g)
    if mats is None:
        a_kk, a_qk = (_scalar_pair_terms(q, k, cum) if scalar
                      else _pair_terms(qf, kf, cum))
        x = _unit_lower_inverse(_as_column(beta) * a_kk)
    else:
        a_kk, a_qk, x = mats
    grown = jnp.exp(cum)
    # [N, 1, d]; [N, 1, 1] for a decay a head: the chunk's whole sum (Mosaic
    # does not cut a row out of an array one lane wide)
    last = (jnp.sum(g, axis=2, keepdims=True) if scalar
            else cum[:, CHUNK - 1:CHUNK])
    shrunk = jnp.exp(last - cum)
    t = (x * beta).astype(mm)
    kg = (kf * grown).astype(mm)
    w = _bmm(t, kg, _NN)
    k_out = kf * shrunk
    decay_head = jnp.exp(last)
    # over the key channels [N, 1, d] either way, so that the states meet
    # one layout
    decay = (jnp.broadcast_to(decay_head, (kf.shape[0], 1, kf.shape[2]))
             if scalar else decay_head)
    # the transposes the loop over chunks would wait for are made here
    return dict(
        qf=qf, kf=kf, cum=cum, a_kk=a_kk, a_qk=a_qk, x=x, grown=grown,
        shrunk=shrunk, decay=decay, t=t, kg=kg, w=w.astype(mm),
        w_t=jnp.swapaxes(w, 1, 2).astype(mm), uv=_bmm(t, v, _NN),
        k_out=k_out.astype(mm), k_out_t=jnp.swapaxes(k_out, 1, 2).astype(mm),
        decay_column=jnp.sum(jnp.where(_eye(kf.shape[-1]), decay, 0.0),
                             axis=2, keepdims=True),
        decay_head=decay_head,
        qg=(qf * grown).astype(mm))


def _heads_in(ref, tokens, heads):
    """A [1, tokens, G d] block as its heads' chunks [G N, C, d]."""
    d = ref.shape[-1] // heads
    x = ref[0]
    return jnp.concatenate(
        [x[:, h * d:(h + 1) * d].reshape(tokens // CHUNK, CHUNK, d)
         for h in range(heads)], axis=0)


def _heads_out(ref, x, tokens, heads):
    """[G N, C, d] into a [1, tokens, G d] block."""
    d, n = x.shape[-1], tokens // CHUNK
    for h in range(heads):
        ref[0, :, h * d:(h + 1) * d] = x[h * n:(h + 1) * n].reshape(
            tokens, d).astype(ref.dtype)


def _stacked(ref):
    """A [1, G, rows, ...] block with its first two axes merged."""
    x = ref[0]
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _decay_in(ref, tokens, heads):
    """The decay's block: a [1, tokens, G d] stream (per key channel) as
    [G N, C, d], or a [1, G, N, 1, C] row a chunk (one a head) as
    [G N, 1, C]."""
    return (_stacked(ref) if len(ref.shape) == 5
            else _heads_in(ref, tokens, heads))


def _by_chunk(x, heads):
    """[G N, ...] -> [G, N, ...]: chunk c of every head is ``[:, c]``."""
    return x.reshape(heads, x.shape[0] // heads, *x.shape[1:])


def _by_head(xs):
    """A list over chunks of [G, ...] -> [G N, ...]."""
    x = jnp.stack(xs, axis=1)
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, tokens,
                heads, keep):
    if keep:
        s0_ref, pair_ref, inv_ref, s_ref = rest
    else:
        (s_ref,) = rest
    n = tokens // CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        s_ref[...] = jnp.zeros(s_ref.shape, _F32)

    q = _heads_in(q_ref, tokens, heads)
    mm = q.dtype
    s = _set_up(q, _heads_in(k_ref, tokens, heads),
                _heads_in(v_ref, tokens, heads),
                _decay_in(g_ref, tokens, heads), _stacked(beta_ref))
    uv, w, k_out_t, decay = (_by_chunk(s[name], heads) for name in (
        "uv", "w", "k_out_t", "decay_column"))
    # the one part that waits for the state: two products a chunk, the
    # program's heads side by side
    state = s_ref[...]                                       # [G, d_k, d_v]
    entering, us = [], []
    for c in range(n):
        if keep:
            s0_ref[0, :, c] = state
        sm = state.astype(mm)
        um = (uv[:, c] - _bmm(w[:, c], sm, _NN)).astype(mm)
        state = state * decay[:, c] + _bmm(k_out_t[:, c], um, _NN)
        entering.append(sm)
        us.append(um)
    s_ref[...] = state

    o = (_bmm(s["qg"], _by_head(entering), _NN)
         + _bmm(s["a_qk"].astype(mm), _by_head(us), _NN))
    _heads_out(o_ref, o, tokens, heads)
    if keep:
        pair_ref[0, :, :, :CHUNK] = s["a_kk"].reshape(heads, tokens, CHUNK)
        pair_ref[0, :, :, CHUNK:] = s["a_qk"].reshape(heads, tokens, CHUNK)
        x = _by_chunk(s["x"], heads)
        for c in range(n):
            inv_ref[(0, slice(None)) + _inv_at(c)] = x[:, c]


def _inv_at(c):
    """Where chunk c's X lies in a block's [tokens / 2, 2 C] stack: two
    chunks side by side, so that the stack's rows fill the 128 lanes."""
    return (slice(c // 2 * CHUNK, (c // 2 + 1) * CHUNK),
            slice(c % 2 * CHUNK, (c % 2 + 1) * CHUNK))


def _specs(tokens, d, heads, at):
    """Block specs, ``heads`` heads a program, of a [B, T, H d] stream, the
    [B, H, chunks, 1, C] beta (a row a chunk), the [B, H, chunks, d, d]
    entering states, the [B, H, T, 2 C] pair terms (A for k | A for q) and
    the [B, H, T / 2, 2 C] inverses; ``at`` maps the grid's block axis to
    the block taken."""
    stream = pl.BlockSpec((1, tokens, heads * d),
                          lambda b, h, n: (b, at(n), h))
    row = pl.BlockSpec((1, heads, tokens // CHUNK, 1, CHUNK),
                       lambda b, h, n: (b, h, at(n), 0, 0))
    states = pl.BlockSpec((1, heads, tokens // CHUNK, d, d),
                          lambda b, h, n: (b, h, at(n), 0, 0))
    pair = pl.BlockSpec((1, heads, tokens, 2 * CHUNK),
                        lambda b, h, n: (b, h, at(n), 0))
    inv = pl.BlockSpec((1, heads, tokens // 2, 2 * CHUNK),
                       lambda b, h, n: (b, h, at(n), 0))
    return stream, row, states, pair, inv


def _forward(q, k, v, g, beta, *, tokens, together, keep, interpret):
    """q, k, v [B, T, H d] (T whole blocks), beta [B, H, T / C, 1, C], g
    like q (per key channel) or like beta (a head); ``together`` heads a
    program."""
    (b, t, hd), heads = q.shape, beta.shape[1]
    d = hd // heads
    stream, row, states, pair, inv = _specs(tokens, d, together, lambda n: n)
    out_shape = [jax.ShapeDtypeStruct(q.shape, v.dtype)]
    out_specs = [stream]
    if keep:
        out_shape += [
            jax.ShapeDtypeStruct((b, heads, t // CHUNK, d, d), _F32),
            jax.ShapeDtypeStruct((b, heads, t, 2 * CHUNK), _F32),
            jax.ShapeDtypeStruct((b, heads, t // 2, 2 * CHUNK), _F32)]
        out_specs += [states, pair, inv]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, tokens=tokens, heads=together,
                          keep=keep),
        grid=(b, heads // together, t // tokens),
        in_specs=[stream, stream, stream, row if g.ndim == 5 else stream,
                  row],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((together, d, d), _F32)],
        interpret=interpret, name="kda_chunk_fwd",
        compiler_params=_PARAMS,
    )(q, k, v, g, beta)
    return out if keep else out[0]


def _pair_terms_bwd(qf, kf, cum, d_kk, d_qk):
    """The pair terms' gradient: (dq, dk, dG) [N, C, d] float32 from the
    cotangents of A_kk (zero unless i < r) and A_qk (zero unless i <= r).
    Off the diagonal the products' own; the reference token's terms cancel
    (A does not depend on it). On the diagonal sub-blocks, a column j at a
    time with E = exp(G_r - G_j): t_k[r] += c_k[r, j] k_j E and t_q
    likewise are the row's gradients, t_i[j] = sum_r (c_k k_r + c_q q_r) E
    the column's, and the decay's is k_r t_k + q_r t_q at the row minus k_j
    t_i at the column (``ops.linear_attention._diagonal_pairs_bwd``)."""
    shape = kf.shape
    c_k = _own_columns(d_kk).reshape(shape[0], CHUNK, SUB)
    c_q = _own_columns(d_qk).reshape(shape[0], CHUNK, SUB)

    def column(j, qf, kf, cum, c_k, c_q, rows, t_k, t_q, t_i):
        """Column j's terms into the three sums, for the sub-blocks'
        ``rows`` given (``_pair_terms``'s ``column``); its own row takes
        the column's sum over them."""
        at_row = j - (SUB - rows)
        decay = jnp.exp(jnp.minimum(cum - _own(cum, at_row, rows), 0.0))
        pairs = _own(kf, at_row, rows) * decay
        ck, cq = c_k[:, :, j:j + 1], c_q[:, :, j:j + 1]
        onto = ((ck * kf + cq * qf) * decay).reshape(-1, rows, kf.shape[-1])
        sums = jnp.broadcast_to(jnp.sum(onto, axis=1, keepdims=True),
                                onto.shape).reshape(kf.shape)
        place = _iota(kf.shape[1:], 0) % rows
        return (t_k + ck * pairs, t_q + cq * pairs,
                jnp.where(place == at_row, sums, t_i))

    t_k, t_q, t_i = (jnp.zeros(shape, _F32) for _ in range(3))
    for j in range(SUB // 2):
        t_k, t_q, t_i = column(j, qf, kf, cum, c_k, c_q, SUB, t_k, t_q, t_i)
    # a column of a sub-block's later half meets rows of that half only
    lower = tuple(map(_lower_rows, (qf, kf, cum, c_k, c_q, t_k, t_q, t_i)))
    sums = lower[5:]
    for j in range(SUB // 2, SUB):
        sums = column(j, *lower[:5], SUB // 2, *sums)
    t_k, t_q, t_i = (_with_lower_rows(full, low)
                     for full, low in zip((t_k, t_q, t_i), sums))
    dq, dk = t_q, t_k + t_i
    dcum = kf * (t_k - t_i) + qf * t_q
    rows_q, rows_k, rows_g = [], [], []
    for s in range(1, CHUNK // SUB):
        rows = slice(s * SUB, (s + 1) * SUB)
        left, right, grown, shrunk = _against_first(qf, kf, cum, s)
        before = _iota((SUB, CHUNK), 1) < s * SUB
        d_off = jnp.concatenate([jnp.where(before, d_kk[:, rows], 0.0),
                                 jnp.where(before, d_qk[:, rows], 0.0)],
                                axis=1)
        d_left = _bmm(d_off, right, _NN, _HIGHEST)           # [N, 2 SUB, d]
        d_right = _bmm(d_off, left, _TN, _HIGHEST)           # [N, C, d]
        rows_k.append(d_left[:, :SUB] * grown)
        rows_q.append(d_left[:, SUB:] * grown)
        rows_g.append(d_left[:, :SUB] * left[:, :SUB]
                      + d_left[:, SUB:] * left[:, SUB:])
        dk = dk + d_right * shrunk
        dcum = dcum - d_right * right
    none = [jnp.zeros((shape[0], SUB, shape[-1]), _F32)]
    return (dq + jnp.concatenate(none + rows_q, axis=1),
            dk + jnp.concatenate(none + rows_k, axis=1),
            dcum + jnp.concatenate(none + rows_g, axis=1))


def _scalar_pair_terms_bwd(qf, kf, cum, a_kk, a_qk, d_kk, d_qk):
    """The factored pair terms' gradient: (dq, dk [N, C, d], dG [N, C, 1])
    float32 from the cotangents of A_kk (zero unless i < r) and A_qk (zero
    unless i <= r). With E = exp(G_r - G_i): the products' cotangents are
    the terms' times E; the decay's is each term times its cotangent,
    summed along its row at r and taken off along its column at i."""
    decay = _scalar_decay_mask(cum)
    c_k, c_q = d_kk * decay, d_qk * decay
    dq = _bmm(c_q, kf, _NN, _HIGHEST)
    dk = (_bmm(c_k, kf, _NN, _HIGHEST) + _bmm(c_k, kf, _TN, _HIGHEST)
          + _bmm(c_q, qf, _TN, _HIGHEST))
    moved = d_kk * a_kk + d_qk * a_qk
    dcum = (jnp.sum(moved, axis=2, keepdims=True)
            - _as_column(jnp.sum(moved, axis=1, keepdims=True)))
    return dq, dk, dcum


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, pair_ref,
                inv_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_ref, *, tokens, heads):
    n = tokens // CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)

    q, k, v = (_heads_in(ref, tokens, heads) for ref in (q_ref, k_ref, v_ref))
    mm = q.dtype
    beta = _stacked(beta_ref)
    pair = pair_ref[0].reshape(heads * n, CHUNK, 2 * CHUNK)
    x = jnp.stack([inv_ref[(0, slice(None)) + _inv_at(c)] for c in range(n)],
                  axis=1).reshape(heads * n, CHUNK, CHUNK)
    s = _set_up(q, k, v, _decay_in(g_ref, tokens, heads), beta,
                (pair[:, :, :CHUNK], pair[:, :, CHUNK:], x))
    scalar = len(g_ref.shape) == 5
    a_kk, a_qk = s["a_kk"], s["a_qk"]
    r, i = _grid_masks()
    s0 = _stacked(s0_ref)                                    # [G N, d_k, d_v]
    s0m = s0.astype(mm)
    um = (s["uv"] - _bmm(s["w"], s0m, _NN)).astype(mm)
    dom = _heads_in(do_ref, tokens, heads)
    # what of dU and of the entering state's cotangent does not wait for
    # the leaving state's
    du_own, ds_own, k_out, w_t, decay = (_by_chunk(a, heads) for a in (
        _bmm(a_qk.astype(mm), dom, _TN), _bmm(s["qg"], dom, _TN),
        s["k_out"], s["w_t"], s["decay_column"]))
    ds = ds_ref[...]                                         # [G, d_k, d_v]
    leaving, dums = [None] * n, [None] * n
    for c in reversed(range(n)):
        leaving[c] = ds
        dum = (du_own[:, c] + _bmm(k_out[:, c], ds.astype(mm), _NN)).astype(
            mm)
        ds = ds * decay[:, c] + ds_own[:, c] - _bmm(w_t[:, c], dum, _NN)
        dums[c] = dum
    ds_ref[...] = ds

    ds1, dum = _by_head(leaving), _by_head(dums)
    ds1m = ds1.astype(mm)
    d_qk = jnp.where(i <= r, _bmm(dom, um, _NT), 0.0)
    dqg = _bmm(dom, s0m, _NT)
    dk_out = _bmm(um, ds1m, _NT)
    dwm = (-_bmm(dum, s0m, _NT)).astype(mm)
    dt = _bmm(dwm, s["kg"], _NT) + _bmm(dum, v, _NT)         # [G N, C, C]
    dkg = _bmm(s["t"], dwm, _TN)
    dv = _bmm(s["t"], dum, _TN)
    dbeta = jnp.sum(dt * x, axis=1, keepdims=True)
    # X = (I + N)^-1: dN = -X^T dX X^T under the diagonal
    dn = jnp.where(i < r, -_bmm(_bmm(x, dt * beta, _TN, _HIGHEST), x, _NT,
                                _HIGHEST), 0.0)
    dbeta = dbeta + _as_row(jnp.sum(dn * a_kk, axis=2, keepdims=True))
    qf, kf, cum = s["qf"], s["kf"], s["cum"]
    d_kk = _as_column(beta) * dn
    grown, shrunk = s["grown"], s["shrunk"]
    k_out_f = kf * shrunk
    # what the chunk's leaving state carries of the entering one, a key
    # channel: its decay's cotangent before the decay itself
    carried = jnp.sum(ds1 * s0, axis=2, keepdims=True)       # [G N, d_k, 1]
    if scalar:
        dq, dk, dcum = _scalar_pair_terms_bwd(qf, kf, cum, a_kk, a_qk, d_kk,
                                              d_qk)
        dcum = dcum + jnp.sum((dkg * kf + dqg * qf) * grown
                              - dk_out * k_out_f, axis=2, keepdims=True)
        dlast = jnp.sum(carried, axis=1, keepdims=True) * s["decay_head"]
        dlast = dlast + jnp.sum(jnp.sum(dk_out * k_out_f, axis=2,
                                        keepdims=True), axis=1, keepdims=True)
    else:
        dq, dk, dcum = _pair_terms_bwd(qf, kf, cum, d_kk, d_qk)
        dcum = dcum + (dkg * kf + dqg * qf) * grown - dk_out * k_out_f
        # the chunk's whole decay: a column over the key channels, made a
        # row
        dlast = jnp.sum(jnp.where(_eye(kf.shape[-1]), carried, 0.0),
                        axis=1, keepdims=True) * s["decay"]
        dlast = dlast + jnp.sum(dk_out * k_out_f, axis=1, keepdims=True)
    dq = dq + dqg * grown
    dk = dk + dkg * grown + dk_out * shrunk
    dcum = dcum + jnp.where(_iota(dcum.shape[1:], 0) == CHUNK - 1, dlast, 0.0)
    _heads_out(dq_ref, dq, tokens, heads)
    _heads_out(dk_ref, dk, tokens, heads)
    _heads_out(dv_ref, dv, tokens, heads)
    if scalar:
        # every later token of the chunk carries this token's decay: the
        # column of sums [G N, C, 1] into a row a chunk, as it came
        dg_ref[0] = jnp.sum(jnp.where(r >= i, dcum, 0.0), axis=1,
                            keepdims=True).reshape(heads, n, 1, CHUNK)
    else:
        _heads_out(dg_ref, _ones_product(i >= r, dcum), tokens, heads)
    dbeta_ref[0] = dbeta.reshape(heads, n, 1, CHUNK)


def _backward(q, k, v, g, beta, s0, pair, inv, do, *, tokens, together,
              interpret):
    (b, t, hd), heads = q.shape, beta.shape[1]
    d = hd // heads
    blocks = t // tokens
    stream, row, states, pair_spec, inv_spec = _specs(
        tokens, d, together, lambda n: blocks - 1 - n)
    decay = row if g.ndim == 5 else stream
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tokens=tokens, heads=together),
        grid=(b, heads // together, blocks),
        in_specs=[stream, stream, stream, decay, row, states, pair_spec,
                  inv_spec, stream],
        out_specs=[stream, stream, stream, decay, row],
        out_shape=[like(q.shape, q.dtype), like(k.shape, k.dtype),
                   like(v.shape, v.dtype), like(g.shape, _F32),
                   like(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((together, d, d), _F32)],
        interpret=interpret, name="kda_chunk_bwd",
        compiler_params=_PARAMS,
    )(q, k, v, g, beta, s0, pair, inv, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(q, k, v, g, beta, tokens, together, interpret):
    return _forward(q, k, v, g, beta, tokens=tokens, together=together,
                    keep=False, interpret=interpret)


def _scan_fwd(q, k, v, g, beta, tokens, together, interpret):
    o, *kept = _forward(q, k, v, g, beta, tokens=tokens, together=together,
                        keep=True, interpret=interpret)
    return o, (q, k, v, g, beta, *kept)


def _scan_bwd(tokens, together, interpret, res, do):
    return tuple(_backward(*res, do, tokens=tokens, together=together,
                           interpret=interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _blocked(q, k, v, g, beta, tokens):
    """The layer's arrays as streams [B, T, H d] — as they come, or from
    [B, T, H, d] heads (on a TPU that reshape is a relayout: a layer that
    can hands over streams) — and its [B, T, H] beta (and a decay a head) a
    row a chunk, the row padded to whole blocks with tokens that write
    nothing and decay nothing."""
    (b, t), h = q.shape[:2], beta.shape[2]
    pad = -t % tokens

    def stream(x, dtype):
        x = x.astype(dtype).reshape(b, t, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def rows(x):
        x = jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(x, 1, 2).reshape(b, h, (t + pad) // CHUNK, 1,
                                             CHUNK)

    return (stream(q, q.dtype), stream(k, q.dtype), stream(v, q.dtype),
            rows(g) if g.shape == beta.shape else stream(g, _F32),
            rows(beta))


def _block_tokens(seq, tokens):
    """Tokens a program takes: ``tokens`` or, for a shorter row, the row in
    whole pairs of chunks."""
    return min(tokens or TOKENS, -(-seq // (2 * CHUNK)) * 2 * CHUNK)


def heads_together(heads, d, want=None):
    """Heads a program takes: the most, up to ``want`` (``TOGETHER``) and
    to ``TOGETHER`` lane groups of 128 in all (the backward's working set at
    four 256-wide heads is past the VMEM it asks for), that divide."""
    most = min(want or TOGETHER, max(1, TOGETHER * 128 // d))
    return max(n for n in range(1, most + 1) if heads % n == 0)


def kda(q, k, v, g, beta, *, tokens=None, together=None, interpret=False):
    """The gated delta rule from a zero state: q, k, v [B, T, H, d] or, the
    kernels' own tiling, [B, T, H d]; g like k (a decay per key channel) or
    [B, T, H] (one a head), beta [B, T, H] -> o shaped like v, in v's dtype
    (the final state stays inside). k and v take q's dtype, the decay and
    beta are float32; differentiable in all five. ``tokens``: what a
    program takes of a row (whole pairs of chunks; ``TOKENS``),
    ``together``: of how many heads (``TOGETHER``)."""
    t, h = q.shape[1], beta.shape[2]
    tokens = _block_tokens(t, tokens)
    d = q.shape[-1] // (h if q.ndim == 3 else 1)
    o = _scan(*_blocked(q, k, v, g, beta, tokens), tokens,
              heads_together(h, d, together), bool(interpret))
    return o[:, :t].reshape(v.shape).astype(v.dtype)


# ------------------------------------------------- the convolution stage
# What a linear-attention layer does between its projections and the scan,
# as one forward and one backward kernel on the [B, T, channels] streams:
# every channel its K taps over the tokens t - K + 1 .. t (zero history
# before a row's start), where the stage has one its bias, SiLU, and for a
# q or k segment the L2 norm over each head's d lanes times a scale —
# ``ops.linear_attention._conv_xla`` is the same function in XLA operations.
# Float32 throughout, and only in VMEM: a program reads a block in the
# stream's dtype and writes one.
#
# A SEGMENT is ``(stream, start, width, scale)``: ``width`` channels of
# input ``stream`` from ``start`` on, L2-normed a head and scaled where
# ``scale`` is a number, left as SiLU made them where it is None; every
# segment leaves as an array of its own. Kimi Delta Attention's q, k, v are
# a segment each of three streams, Gated DeltaNet's are three of its one
# q | k | v stream — the body is the same. The grid is (batch, channel
# steps, token blocks): a program takes one token block of EVERY segment,
# each ``width / steps`` channels wide (whole heads), so q, k and v share
# a call; tokens are innermost and sequential.
#
# - ``conv_streams_fwd`` carries a block's last rows to the next in VMEM
#   scratch (the K - 1 earlier tokens; zeros at a row's start).
# - ``conv_streams_bwd`` keeps nothing of the forward but x and the taps: it
#   rebuilds the pre-activation and the norm from x (the rows before the
#   block arrive as a 16-row block of their own), walks the token blocks
#   from the last to the first with the first rows of the pre-activation's
#   gradient in scratch (what the earlier block's dx needs), and sums the
#   taps' gradient [K, channels] over the tokens in float32, one a batch
#   row.
#
# The body runs a head's lanes at a time (columns are independent but for
# the head's sums): its float32 temporaries are [tokens, d].

#: tokens a program of the convolution stage takes
CONV_TOKENS = 256
#: channels it takes of the narrowest segment
CONV_LANES = 512
#: rows carried between token blocks: one float32 tile, so K - 1 <= 8
CONV_HALO = 8
#: rows of the block that brings the backward the tokens before its own
_BEFORE = 16


def conv_steps(segments, head, lanes=None):
    """Channel steps of the convolution stage's grid for these segments
    (``(stream, start, width, scale)``) and this head width: the most that
    leave the narrowest segment ``lanes`` channels a program, every
    segment whole heads (whole lane groups where it is not normed) and
    starting on a block of its own width. 0 where no cut serves."""
    lanes = lanes or CONV_LANES
    narrow = min(width for _, _, width, _ in segments)

    def cuts(n):
        return all(
            width % n == 0 and start % (width // n) == 0
            and (width // n) % (128 if scale is None else head) == 0
            for _, start, width, scale in segments)

    return next((n for n in range(max(1, narrow // lanes), 0, -1)
                 if cuts(n)), 0)


def conv_supported(segments, head, taps, dtype):
    """Whether the kernels take these segments: where one is normed, heads
    that fill whole lane groups (an un-normed segment is cut by lane groups
    whatever the head), taps whose history fits the carried rows, bf16 or
    float32 streams, and a channel cut (``conv_steps``)."""
    return (all(scale is None or head % 128 == 0
                for _, _, _, scale in segments)
            and 1 <= taps <= CONV_HALO + 1
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and conv_steps(segments, head) > 0)


def _shifted(ext, taps, up=False):
    """[x[t - s] for s < taps] from ``ext`` = the CONV_HALO rows before
    the block on top of the block's own; with ``up``, [x[t + s]] from the
    block's rows on top of the rows after it. Whole-tile rotations and
    aligned slices."""
    rows = ext.shape[0] - CONV_HALO
    if up:
        return [ext[:rows]] + [
            pltpu.roll(ext, ext.shape[0] - s, 0)[:rows]
            for s in range(1, taps)]
    return [ext[CONV_HALO:]] + [pltpu.roll(ext, s, 0)[CONV_HALO:]
                                for s in range(1, taps)]


def _pre_activation(shifted, w):
    """sum_s w[K - 1 - s] x[t - s]: tap K - 1 meets token t."""
    taps = len(shifted)
    return sum(shifted[s] * w[taps - 1 - s:taps - s] for s in range(taps))


def _head_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _conv_fwd_kernel(*refs, segments, head, eps, bias):
    n = len(segments)
    xs, ws, outs, halos = (refs[i * n:(i + 1) * n] for i in range(4))

    @pl.when(pl.program_id(2) == 0)
    def _row_start():
        for halo in halos:
            halo[...] = jnp.zeros(halo.shape, _F32)

    for x_ref, w_ref, o_ref, halo, (_, _, _, scale) in zip(
            xs, ws, outs, halos, segments):
        tokens, width = x_ref.shape[1:]
        taps = w_ref.shape[1] - bias
        step = 128 if scale is None else head
        for at in range(0, width, step):
            lanes = slice(at, at + step)
            xf = x_ref[0, :, lanes].astype(_F32)
            ext = jnp.concatenate([halo[:, lanes], xf], axis=0)
            halo[:, lanes] = xf[tokens - CONV_HALO:]
            shifted = _shifted(ext, taps)
            w = w_ref[0, :, lanes].astype(_F32)
            a = _pre_activation(shifted, w)
            if bias:
                a = a + w[taps:]
            y = a * jax.nn.sigmoid(a)
            if scale is not None:
                y = y * (jax.lax.rsqrt(_head_sum(y * y) + eps) * scale)
            o_ref[0, :, lanes] = y.astype(o_ref.dtype)


def _conv_bwd_kernel(*refs, segments, head, eps, bias):
    n = len(segments)
    xs, befores, ws, dys, dxs, dws, carries = (
        refs[i * n:(i + 1) * n] for i in range(7))
    first = pl.program_id(2) == 0
    # the grid's last token step is the row's first block: zero history
    row_start = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _row_end():
        for dw_ref, carry in zip(dws, carries):
            dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)
            carry[...] = jnp.zeros(carry.shape, _F32)

    for (x_ref, before_ref, w_ref, dy_ref, dx_ref, dw_ref, carry,
         (_, _, _, scale)) in zip(xs, befores, ws, dys, dxs, dws, carries,
                                  segments):
        tokens, width = x_ref.shape[1:]
        taps = w_ref.shape[1] - bias
        step = 128 if scale is None else head
        for at in range(0, width, step):
            lanes = slice(at, at + step)
            w = w_ref[0, :, lanes].astype(_F32)
            before = before_ref[0, :, lanes].astype(_F32)[
                _BEFORE - CONV_HALO:]
            ext = jnp.concatenate(
                [jnp.where(row_start, 0.0, before),
                 x_ref[0, :, lanes].astype(_F32)], axis=0)
            shifted = _shifted(ext, taps)
            a = _pre_activation(shifted, w)
            if bias:
                a = a + w[taps:]
            sig = jax.nn.sigmoid(a)
            dy = dy_ref[0, :, lanes].astype(_F32)
            if scale is not None:
                # o = scale y r, r = rsqrt(sum_d y^2 + eps):
                # dy = scale r (do - y r^2 <do, y>)
                y = a * sig
                r = jax.lax.rsqrt(_head_sum(y * y) + eps)
                dy = (dy - y * (r * r * _head_sum(dy * y))) * (r * scale)
            da = dy * (sig * (1.0 + a * (1.0 - sig)))
            # the taps' gradient and, in its row, the bias's
            dw_ref[0, :, lanes] += jnp.concatenate(
                [jnp.sum(da * shifted[taps - 1 - j], axis=0, keepdims=True)
                 for j in range(taps)]
                + ([jnp.sum(da, axis=0, keepdims=True)] if bias else []),
                axis=0)
            later = _shifted(jnp.concatenate([da, carry[:, lanes]], axis=0),
                             taps, up=True)
            carry[:, lanes] = da[:CONV_HALO]
            dx_ref[0, :, lanes] = _pre_activation(later, w).astype(
                dx_ref.dtype)


def _conv_specs(segments, tokens, steps, rows, token_block):
    """A segment's BlockSpecs — its block of its stream, of the ``rows``
    rows of the stream's taps (the bias's among them), of an array of its
    own (q, k or v; a cotangent; dx) — and its block's width;
    ``token_block``: the grid's token step -> the row's."""
    streams, weights, own, widths = [], [], [], []
    for stream, start, width, _ in segments:
        wide = width // steps
        first = start // wide
        streams.append(pl.BlockSpec(
            (1, tokens, wide),
            lambda b, c, n, first=first: (b, token_block(n), first + c)))
        weights.append(pl.BlockSpec(
            (1, rows, wide), lambda b, c, n, first=first: (b, 0, first + c)))
        own.append(pl.BlockSpec(
            (1, tokens, wide), lambda b, c, n: (b, token_block(n), c)))
        widths.append(wide)
    return streams, weights, own, widths


_CONV_STATIC = ("segments", "head", "eps", "tokens", "steps", "interpret",
                "bias")


# jitted, as the backward is: a layer's call sites (forward, recomputed
# forward, backward, in every layer and every program a start traces) then
# share ONE trace and one lowering of the kernel's body a shape, where each
# would run its Python again — 0.2 s a site, 48 sites a start of the
# Kimi-Linear cell
@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_forward(xs, ws, *, segments, head, eps, tokens, steps, interpret,
                  bias):
    b, t, _ = xs[0].shape
    streams, weights, own, widths = _conv_specs(
        segments, tokens, steps, ws[0].shape[1], lambda n: n)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, segments=segments, head=head,
                          eps=eps, bias=bias),
        grid=(b, steps, t // tokens),
        in_specs=streams + weights, out_specs=own,
        out_shape=[jax.ShapeDtypeStruct((b, t, width), xs[s].dtype)
                   for s, _, width, _ in segments],
        scratch_shapes=[pltpu.VMEM((CONV_HALO, wide), _F32)
                        for wide in widths],
        interpret=interpret, name="conv_streams_fwd",
        compiler_params=_PARAMS,
    )(*(xs[s] for s, *_ in segments), *(ws[s] for s, *_ in segments))


@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_backward(xs, ws, dys, *, segments, head, eps, tokens, steps,
                   interpret, bias):
    b, t, _ = xs[0].shape
    rows = ws[0].shape[1]               # the taps and, with one, the bias
    blocks = t // tokens

    def rev(n):
        return blocks - 1 - n

    streams, weights, own, widths = _conv_specs(
        segments, tokens, steps, rows, rev)
    befores = [
        pl.BlockSpec(
            (1, _BEFORE, wide),
            lambda b_, c, n, first=start // wide: (
                b_, jnp.maximum(rev(n) * (tokens // _BEFORE) - 1, 0),
                first + c))
        for (_, start, _, _), wide in zip(segments, widths)]
    taps_own = [pl.BlockSpec((1, rows, wide), lambda b_, c, n: (b_, 0, c))
                for wide in widths]
    like = jax.ShapeDtypeStruct
    of = [s for s, *_ in segments]
    grads = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, segments=segments, head=head,
                          eps=eps, bias=bias),
        grid=(b, steps, blocks),
        in_specs=streams + befores + weights + own,
        out_specs=own + taps_own,
        out_shape=[like((b, t, width), xs[s].dtype)
                   for s, _, width, _ in segments]
        + [like((b, rows, width), _F32) for _, _, width, _ in segments],
        scratch_shapes=[pltpu.VMEM((CONV_HALO, wide), _F32)
                        for wide in widths],
        interpret=interpret, name="conv_streams_bwd",
        compiler_params=_PARAMS,
    )(*(xs[s] for s in of), *(xs[s] for s in of), *(ws[s] for s in of),
      *dys)
    n = len(segments)

    def by_stream(parts, dtypes):
        """A stream's segments side by side again, in its dtype."""
        return tuple(
            jnp.concatenate([p for p, s in zip(parts, of) if s == i],
                            axis=-1).astype(dtype)
            for i, dtype in enumerate(dtypes))

    return (by_stream(grads[:n], [x.dtype for x in xs]),
            by_stream(grads[n:], [w.dtype for w in ws]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(xs, ws, static):
    return tuple(_conv_forward(xs, ws, **dict(static)))


def _conv_fwd(xs, ws, static):
    return _conv(xs, ws, static), (xs, ws)


def _conv_bwd(static, res, dys):
    return _conv_backward(*res, dys, **dict(static))


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_streams(xs, ws, segments, *, head, eps, bias=False, tokens=None,
                 lanes=None, interpret=False):
    """The convolution stage: streams ``xs`` [B, T, C_i], their taps ``ws``
    [B, K, C_i] (a copy a batch row: the taps' gradient leaves a row at a
    time) and ``segments`` ``(stream, start, width, scale)`` that cover
    every stream in order -> one [B, T, width] array a segment, in its
    stream's dtype: the causal depthwise convolution and SiLU, and where
    ``scale`` is a number the L2 norm over each ``head`` lanes times it.
    With ``bias`` (static) every ``ws`` is [B, K + 1, C_i], row K the
    stream's bias, added to the taps' sum before SiLU. Differentiable in
    the streams and the taps (the bias's gradient is row K of theirs); what
    a backward pass keeps is those. ``tokens``: what a program takes of a
    row (a multiple of 16; ``CONV_TOKENS``; a shorter row is padded to it),
    ``lanes``: of the narrowest segment's channels (``CONV_LANES``)."""
    segments = tuple(tuple(s) for s in segments)
    steps = conv_steps(segments, head, lanes)
    if not steps:
        raise ValueError(f"no channel cut serves the segments {segments} "
                         f"with heads of {head}")
    t = xs[0].shape[1]
    tokens = tokens or CONV_TOKENS
    pad = -t % tokens
    if pad:
        xs = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in xs)
    static = (("segments", segments), ("head", int(head)),
              ("eps", float(eps)), ("tokens", int(tokens)),
              ("steps", steps), ("interpret", bool(interpret)),
              ("bias", bool(bias)))
    outs = _conv(tuple(xs), tuple(ws), static)
    return tuple(o[:, :t] for o in outs) if pad else outs


# ------------------------------------------- the gated short convolution
# LFM2's stage between its two projections, ``C * conv(B * u)`` on the ONE
# [B, T, 3 C] stream ``in_proj`` leaves (thirds B | C | u in that order):
# a gate before the K taps, no activation, a gate after —
# ``ops.linear_attention._gated_xla`` is the same function in XLA
# operations. Another algebra than the convolution stage's above, so other
# bodies; they share its pure helpers (``_shifted``, ``_pre_activation``,
# the carried rows). Float32 throughout, and only in VMEM. The grid is
# (row, token blocks), tokens sequential; a program holds every channel of
# its tokens, a lane group at a time in the body.
#
# - ``gated_conv_fwd`` reads its block of B, of C and of u out of the one
#   array (three BlockSpecs, a third's width apart) and carries the last
#   rows of ``B * u`` to the next block in VMEM scratch (zeros at a row's
#   first block).
# - ``gated_conv_bwd`` keeps nothing of the forward but ``bcu`` and the
#   taps. It walks the token blocks from the last to the first, rebuilds
#   ``B * u`` and ``conv(B * u)`` (the rows before the block arrive as a
#   16-row block of their own), carries the first rows of the convolution's
#   cotangent for the earlier block, sums the taps' gradient [K, C] over
#   the tokens in float32, one a batch row, and writes ``d bcu`` as ONE
#   [B, T, 3 C] array, thirds side by side as they came (three arrays and
#   XLA's concatenation: 2.86 ms against 1.64 at the LFM2 cell's shape,
#   ``tools/shortconv_bench.py``).

#: bytes of a program's block of ONE third: 256 tokens of 2,048 bf16
#: channels. On the v5e 128 to 512 tokens of them, and a third cut in
#: channel steps, all move the same GB/s (``tools/shortconv_bench.py``)
GATED_BLOCK_BYTES = 2**20
_GATED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def gated_conv_tokens(channels, dtype):
    """Tokens a program takes of a stream of 3 x ``channels`` in ``dtype``:
    ``GATED_BLOCK_BYTES`` a third in whole 16-row tiles, 512 at most; under
    16 (the rows the backward is brought from before its block) where a
    third is too wide."""
    most = GATED_BLOCK_BYTES // (channels * jnp.dtype(dtype).itemsize)
    return min(512, most // _BEFORE * _BEFORE)


def gated_conv_supported(channels, taps, dtype):
    """Whether the kernels take this stage: channels that fill whole lane
    groups, taps whose history fits the carried rows, a bf16 or float32
    stream, and a block of at least 16 tokens."""
    return (channels % 128 == 0 and 1 <= taps <= CONV_HALO + 1
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and gated_conv_tokens(channels, dtype) >= _BEFORE)


def _gated_fwd_kernel(b_ref, c_ref, u_ref, w_ref, o_ref, halo):
    @pl.when(pl.program_id(1) == 0)
    def _row_start():
        halo[...] = jnp.zeros(halo.shape, _F32)

    tokens, channels = o_ref.shape[1:]
    taps = w_ref.shape[1]
    for at in range(0, channels, 128):
        lanes = slice(at, at + 128)
        m = (b_ref[0, :, lanes].astype(_F32)
             * u_ref[0, :, lanes].astype(_F32))
        ext = jnp.concatenate([halo[:, lanes], m], axis=0)
        halo[:, lanes] = m[tokens - CONV_HALO:]
        mixed = _pre_activation(_shifted(ext, taps),
                                w_ref[0, :, lanes].astype(_F32))
        o_ref[0, :, lanes] = (c_ref[0, :, lanes].astype(_F32)
                              * mixed).astype(o_ref.dtype)


def _gated_bwd_kernel(bcu_ref, before_ref, w_ref, dy_ref, dbcu_ref, dw_ref,
                      carry):
    # the grid's last token step is the row's first block: zero history
    row_start = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _row_end():
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)
        carry[...] = jnp.zeros(carry.shape, _F32)

    channels = dy_ref.shape[2]
    taps = w_ref.shape[1]
    for at in range(0, channels, 128):
        lanes = slice(at, at + 128)
        b_at, c_at, u_at = (slice(i * channels + at, i * channels + at + 128)
                            for i in range(3))
        w = w_ref[0, :, lanes].astype(_F32)
        b = bcu_ref[0, :, b_at].astype(_F32)
        u = bcu_ref[0, :, u_at].astype(_F32)
        before = (before_ref[0, :, b_at].astype(_F32)
                  * before_ref[0, :, u_at].astype(_F32))[_BEFORE - CONV_HALO:]
        shifted = _shifted(jnp.concatenate(
            [jnp.where(row_start, 0.0, before), b * u], axis=0), taps)
        dy = dy_ref[0, :, lanes].astype(_F32)
        dbcu_ref[0, :, c_at] = (dy * _pre_activation(shifted, w)).astype(
            dbcu_ref.dtype)
        da = dy * bcu_ref[0, :, c_at].astype(_F32)
        dw_ref[0, :, lanes] += jnp.concatenate(
            [jnp.sum(da * shifted[taps - 1 - j], axis=0, keepdims=True)
             for j in range(taps)], axis=0)
        dm = _pre_activation(_shifted(
            jnp.concatenate([da, carry[:, lanes]], axis=0), taps, up=True), w)
        carry[:, lanes] = da[:CONV_HALO]
        dbcu_ref[0, :, b_at] = (dm * u).astype(dbcu_ref.dtype)
        dbcu_ref[0, :, u_at] = (dm * b).astype(dbcu_ref.dtype)


# jitted for the reason ``_conv_forward`` is: one trace and one lowering of
# a body a shape, whatever the number of call sites
@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def _gated_forward(bcu, w, *, tokens, interpret):
    b, t, c3 = bcu.shape
    channels, taps = c3 // 3, w.shape[1]
    thirds = [pl.BlockSpec((1, tokens, channels),
                           lambda b_, n, third=i: (b_, n, third))
              for i in range(3)]
    return pl.pallas_call(
        _gated_fwd_kernel, grid=(b, t // tokens),
        in_specs=thirds + [pl.BlockSpec((1, taps, channels),
                                        lambda b_, n: (b_, 0, 0))],
        out_specs=thirds[0],
        out_shape=jax.ShapeDtypeStruct((b, t, channels), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((CONV_HALO, channels), _F32)],
        interpret=interpret, name="gated_conv_fwd",
        compiler_params=_GATED_PARAMS,
    )(bcu, bcu, bcu, w)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def _gated_backward(bcu, w, dy, *, tokens, interpret):
    b, t, c3 = bcu.shape
    channels, taps = c3 // 3, w.shape[1]
    blocks = t // tokens

    def rev(n):
        return blocks - 1 - n

    def whole(wide):
        return pl.BlockSpec((1, tokens, wide), lambda b_, n: (b_, rev(n), 0))

    before = pl.BlockSpec(
        (1, _BEFORE, c3), lambda b_, n: (
            b_, jnp.maximum(rev(n) * (tokens // _BEFORE) - 1, 0), 0))
    taps_row = pl.BlockSpec((1, taps, channels), lambda b_, n: (b_, 0, 0))
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        _gated_bwd_kernel, grid=(b, blocks),
        in_specs=[whole(c3), before, taps_row, whole(channels)],
        out_specs=[whole(c3), taps_row],
        out_shape=[like(bcu.shape, bcu.dtype),
                   like((b, taps, channels), _F32)],
        scratch_shapes=[pltpu.VMEM((CONV_HALO, channels), _F32)],
        interpret=interpret, name="gated_conv_bwd",
        compiler_params=_GATED_PARAMS,
    )(bcu, bcu, w, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated(bcu, w, tokens, interpret):
    return _gated_forward(bcu, w, tokens=tokens, interpret=interpret)


def _gated_fwd(bcu, w, tokens, interpret):
    return _gated(bcu, w, tokens, interpret), (bcu, w)


def _gated_bwd(tokens, interpret, res, dy):
    bcu, w = res
    dbcu, dw = _gated_backward(bcu, w, dy, tokens=tokens,
                               interpret=interpret)
    return dbcu, dw.astype(w.dtype)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_conv(bcu, w, *, tokens=None, interpret=False):
    """The gated short convolution: ``bcu`` [B, T, 3 C] (thirds B | C | u)
    and its taps ``w`` [B, K, C] (a copy a batch row: the taps' gradient
    leaves a row at a time) -> ``C * conv(B * u)`` [B, T, C] in bcu's
    dtype, the convolution causal and depthwise with zero history before a
    row's first token. Differentiable in both; what a backward pass keeps
    is those. ``tokens``: what a program takes of a row (a multiple of 16;
    ``gated_conv_tokens``; a shorter row is padded to it)."""
    t = bcu.shape[1]
    tokens = int(tokens or gated_conv_tokens(w.shape[2], bcu.dtype))
    pad = -t % tokens
    if pad:
        bcu = jnp.pad(bcu, ((0, 0), (0, pad), (0, 0)))
    y = _gated(bcu, w, tokens, bool(interpret))
    return y[:, :t] if pad else y
