"""The softmax-attention stem's stage between the projections and the core
as two Pallas TPU kernels under a ``jax.custom_vjp``: ``qk_heads_fwd`` and
``qk_heads_bwd``. A head of q and of k passes an RMSNorm over its d features
(weight, or 1 + weight) and, where the layer has positions, rotate-half RoPE
over the first ``rotary_dim`` of them, and leaves in the core's layout:
streams [B, T, heads x d] in, head arrays [B, heads, T, d] out —
``ops.attention._qk_xla`` is the same function in XLA operations. Float32
throughout, and only in VMEM: a program reads a block in the stream's dtype
and writes one, rounded once.

The grid is (batch, token blocks, head steps), heads innermost. A step takes
``heads / steps`` query heads — a ``[tokens, heads x d]`` column block of the
q stream, written as a ``[1, heads, tokens, d]`` block of the head array: THE
HEAD SPLIT IS THE BLOCKSPEC'S INDEX MAP and costs no pass — and, every
``steps / kv_heads``-th step, one key head the same way (its blocks do not
move between, so they are fetched and written once). Where the query
projection lays a head out as [query d | gate d] (``stride`` 2: Qwen3-Next) a
step is one head, its block every ``stride``-th d-wide column block; the
gate's columns are never read.

The rotation is lane rolls of the normed row times tables made once outside
(``rope_tables``: float32 [1 + rolls, T, span], ``span`` the whole lane
groups that hold the rotated features; their block index does not change
over the head steps, so a token block fetches them once): ``x cos + roll(x,
rotary_dim / 2) sin`` with the sign and the zeros of the features that pass
in the ``sin`` tables, one roll where the rotated features fill their lane
groups, two (up and down) where they do not. No slice or concatenate at lane
64.

``qk_heads_bwd`` keeps nothing of the forward but the streams and the
weights: it rebuilds the normed float32 in VMEM, applies the transposed
rotation (the same rolls, the ``sin`` tables negated) and the norm's
backward, writes dq and dk as streams and the weights' gradients as a
float32 partial sum a (row, token block, head), summed outside.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
LANES = 128
#: tokens a program takes of a row
QK_TOKENS = 512
#: most lanes it takes of the q stream: the query heads of a step
QK_LANES = 1024

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2**20)


def rope_rolls(d, rotary_dim):
    """``(span, rolls)`` of a rotate-half rotation of the first
    ``rotary_dim`` of d features as lane rolls: the rolls run over the first
    ``span`` lanes (the whole lane groups that hold the rotated features);
    feature i < rotary_dim / 2 meets i + rotary_dim / 2 and the reverse,
    which is ONE circular roll by half where the rotated features fill the
    span, else one down and one up."""
    half = rotary_dim // 2
    span = -(-rotary_dim // LANES) * LANES
    return span, ((half,) if rotary_dim == span else (half, span - half))


def supported(heads, kv_heads, d, dtype, rotary_dim):
    """Whether the kernels take these heads: a width that fills whole lane
    groups, query heads in whole groups a key head, bf16 or float32
    streams, and a rotation (``rotary_dim``; None: no positions) of all d
    features or of an even number within the first lane group."""
    return (d % LANES == 0 and kv_heads > 0 and heads % kv_heads == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and (rotary_dim is None or rotary_dim == d
                 or (rotary_dim % 2 == 0 and 0 < rotary_dim <= LANES)))


def rope_tables(seq, d, rotary_dim, base):
    """The rotation's float32 tables [1 + rolls, seq, span] for positions
    0 .. seq - 1: ``cos`` (1 on a feature that passes), then a ``sin`` table
    a roll of ``rope_rolls``, signed as rotate-half signs the partner it
    brings (- for the upper partner, + for the lower) and 0 elsewhere. The
    angles are ``text.models._rope``'s to the letter."""
    span, rolls = rope_rolls(d, rotary_dim)
    half = rotary_dim // 2
    inv = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=_F32)
                          / rotary_dim))
    freqs = jnp.outer(jnp.arange(seq), inv)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    zeros = jnp.zeros((seq, half), _F32)
    rest = jnp.zeros((seq, span - rotary_dim), _F32)
    down = jnp.concatenate([zeros, sin, rest], axis=1)   # x[i - half] here
    up = jnp.concatenate([-sin, zeros, rest], axis=1)    # x[i + half] here
    return jnp.stack(
        [jnp.concatenate([cos, cos, rest + 1.0], axis=1)]
        + ([down + up] if len(rolls) == 1 else [down, up]))


def _rotated(x, tab_ref, rolls, sign):
    """The rotation (``sign`` +1) or its transpose (-1) of x [tokens, span]
    float32 by the block's tables."""
    y = x * tab_ref[0]
    for i, shift in enumerate(rolls):
        partner = pltpu.roll(x, shift, 1) * tab_ref[1 + i]
        y = y + partner if sign > 0 else y - partner
    return y


def _head_mean(x):
    return jnp.sum(x, axis=1, keepdims=True) * (1.0 / x.shape[1])


# A head's whole [tokens, d] block goes through each operation at once: the
# compiler overlaps one row group's lane sum and rsqrt with the next one's
# loads. (A loop over row chunks that stay in registers serialises on those
# latencies: 5 x slower at 64 rows a chunk, PERF.md section 6, PR 45.)
def _heads_of(x_ref, w_ref, d, zero_centered):
    """(number, lanes, float32 block, the weight's scale [1, d]) a head of
    a stream's block."""
    for j in range(x_ref.shape[2] // d):
        lanes = slice(j * d, (j + 1) * d)
        w = w_ref[0, :, lanes].astype(_F32)
        yield j, lanes, x_ref[0, :, lanes].astype(_F32), (
            1.0 + w if zero_centered else w)


def _every(k_every, body):
    """``body()`` on the steps that take a key head."""
    if k_every == 1:
        body()
    else:
        pl.when(pl.program_id(2) % k_every == 0)(body)


def _fwd_kernel(*refs, d, k_every, rolls, span, eps, zero_centered):
    q_ref, k_ref, wq_ref, wk_ref, *rest = refs
    tab_ref = rest.pop(0) if rolls else None
    oq_ref, ok_ref = rest

    def stream(x_ref, w_ref, o_ref):
        for j, _, x, scale in _heads_of(x_ref, w_ref, d, zero_centered):
            y = x * jax.lax.rsqrt(_head_mean(x * x) + eps) * scale
            if not rolls:
                o_ref[0, j] = y.astype(o_ref.dtype)
                continue
            o_ref[0, j, :, :span] = _rotated(
                y[:, :span], tab_ref, rolls, 1).astype(o_ref.dtype)
            if span < d:
                o_ref[0, j, :, span:] = y[:, span:].astype(o_ref.dtype)

    stream(q_ref, wq_ref, oq_ref)
    _every(k_every, lambda: stream(k_ref, wk_ref, ok_ref))


def _bwd_kernel(*refs, d, stride, k_every, rolls, span, eps, zero_centered):
    q_ref, k_ref, wq_ref, wk_ref, *rest = refs
    tab_ref = rest.pop(0) if rolls else None
    dyq_ref, dyk_ref, dq_ref, dk_ref, dwq_ref, dwk_ref = rest

    def stream(x_ref, w_ref, dy_ref, dx_ref, dw_ref, stride):
        for j, lanes, x, scale in _heads_of(x_ref, w_ref, d, zero_centered):
            # y = R(n scale), n = x r, r = rsqrt(mean_d x^2 + eps):
            # dn = R^T(dy) scale, dx = r (dn - n mean_d(dn n))
            r = jax.lax.rsqrt(_head_mean(x * x) + eps)
            n = x * r
            dy = dy_ref[0, j].astype(_F32)
            if rolls:
                turned = _rotated(dy[:, :span], tab_ref, rolls, -1)
                dy = turned if span == d else jnp.concatenate(
                    [turned, dy[:, span:]], axis=1)
            dw_ref[0, 0, :, lanes] = jnp.sum(dy * n, axis=0, keepdims=True)
            dn = dy * scale
            at = j * stride * d
            dx_ref[0, :, at:at + d] = (
                r * (dn - n * _head_mean(dn * n))).astype(dx_ref.dtype)
            if stride > 1:
                # the columns between the queries (a gate's) took no part
                dx_ref[0, :, at + d:at + stride * d] = jnp.zeros(
                    (x.shape[0], (stride - 1) * d), dx_ref.dtype)

    stream(q_ref, wq_ref, dyq_ref, dq_ref, dwq_ref, stride)
    _every(k_every,
           lambda: stream(k_ref, wk_ref, dyk_ref, dk_ref, dwk_ref, 1))


def _specs(d, tokens, hq, k_every, stride, rolls, span):
    """BlockSpecs of a grid step (b, n, g): the q stream's and the k
    stream's column block (q's gradient takes the columns between its
    heads too), a weight's row, their head-array blocks, their partial-sum
    blocks, the token block's tables."""
    def col(width, index):
        return pl.BlockSpec((1, tokens, width),
                            lambda b, n, g: (b, n, index(g)))

    def row(width, index):
        return pl.BlockSpec((1, 1, width), lambda b, n, g: (b, 0, index(g)))

    def heads(count, index):
        return pl.BlockSpec((1, count, tokens, d),
                            lambda b, n, g: (b, index(g), n, 0))

    def parts(width, index):
        return pl.BlockSpec((1, 1, 1, width),
                            lambda b, n, g: (b, n, 0, index(g)))

    def q_at(g):
        return g

    def k_at(g):
        return g // k_every

    return dict(
        q=col(hq * d, lambda g: g * stride), k=col(d, k_at),
        dq=col(hq * stride * d, q_at),
        wq=row(hq * d, q_at), wk=row(d, k_at),
        q_heads=heads(hq, q_at), k_heads=heads(1, k_at),
        q_parts=parts(hq * d, q_at), k_parts=parts(d, k_at),
        tables=pl.BlockSpec((len(rolls) + 1, tokens, span),
                            lambda b, n, g: (0, n, 0)))


_STATIC = ("d", "stride", "steps", "tokens", "rotary_dim", "base", "eps",
           "zero_centered", "interpret")


def _plan(q, wq, wk, d, stride, steps, tokens, rotary_dim, base):
    """What both calls share: the grid, the head counts, the block specs,
    the rotation's rolls and its tables (made where they are used — they
    come from nothing a step holds, so a backward makes them again and
    keeps none; inside the inner jit they are traced once a shape)."""
    b, t, _ = q.shape
    heads, kv_heads = wq.shape[2] // d, wk.shape[2] // d
    k_every = steps // kv_heads
    span, rolls = rope_rolls(d, rotary_dim) if rotary_dim else (0, ())
    at = _specs(d, tokens, heads // steps, k_every, stride, rolls, span)
    tables = [rope_tables(t, d, rotary_dim, base)] if rolls else []
    return ((b, t // tokens, steps), heads, kv_heads, at, tables,
            dict(d=d, k_every=k_every, rolls=rolls, span=span))


# jitted, as the convolution stage's calls are: a layer's call sites
# (forward, recomputed forward, backward, in every layer) share ONE trace
# and one lowering of the kernel's body a shape
@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, wq, wk, *, d, stride, steps, tokens, rotary_dim, base,
             eps, zero_centered, interpret):
    grid, heads, kv_heads, at, tables, body = _plan(
        q, wq, wk, d, stride, steps, tokens, rotary_dim, base)
    b, t, _ = q.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, zero_centered=zero_centered,
                          **body),
        grid=grid,
        in_specs=[at["q"], at["k"], at["wq"], at["wk"]]
        + [at["tables"]] * len(tables),
        out_specs=[at["q_heads"], at["k_heads"]],
        out_shape=[jax.ShapeDtypeStruct((b, heads, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, kv_heads, t, d), k.dtype)],
        interpret=interpret, name="qk_heads_fwd", compiler_params=_PARAMS,
    )(q, k, wq, wk, *tables)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, wq, wk, dyq, dyk, *, d, stride, steps, tokens,
              rotary_dim, base, eps, zero_centered, interpret):
    grid, heads, kv_heads, at, tables, body = _plan(
        q, wq, wk, d, stride, steps, tokens, rotary_dim, base)
    like = jax.ShapeDtypeStruct
    b, blocks, _ = grid
    dq, dk, dwq, dwk = pl.pallas_call(
        functools.partial(_bwd_kernel, stride=stride, eps=eps,
                          zero_centered=zero_centered, **body),
        grid=grid,
        in_specs=[at["q"], at["k"], at["wq"], at["wk"]]
        + [at["tables"]] * len(tables) + [at["q_heads"], at["k_heads"]],
        out_specs=[at["dq"], at["k"], at["q_parts"], at["k_parts"]],
        out_shape=[like(q.shape, q.dtype), like(k.shape, k.dtype),
                   like((b, blocks, 1, heads * d), _F32),
                   like((b, blocks, 1, kv_heads * d), _F32)],
        interpret=interpret, name="qk_heads_bwd", compiler_params=_PARAMS,
    )(q, k, wq, wk, *tables, dyq, dyk)
    return (dq, dk, jnp.sum(dwq, axis=1).astype(wq.dtype),
            jnp.sum(dwk, axis=1).astype(wk.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _qk(q, k, wq, wk, static):
    return tuple(_forward(q, k, wq, wk, **dict(static)))


def _qk_fwd(q, k, wq, wk, static):
    return _qk(q, k, wq, wk, static), (q, k, wq, wk)


def _qk_bwd(static, kept, dys):
    return _backward(*kept, *dys, **dict(static))


_qk.defvjp(_qk_fwd, _qk_bwd)


def qk_steps(heads, kv_heads, d, stride, lanes=None):
    """Head steps of the grid: a multiple of the key heads, the fewest
    that leave a step at most ``lanes`` lanes of query heads — and every
    query head a step of its own where the stream holds other columns
    between them."""
    group = heads // kv_heads
    most = 1 if stride > 1 else max(1, (lanes or QK_LANES) // d)
    return kv_heads * next(m for m in range(1, group + 1)
                           if group % m == 0 and group // m <= most)


def qk_heads(q, k, wq, wk, *, d, zero_centered, eps, rotary_dim, base,
             stride=1, tokens=None, lanes=None, interpret=False):
    """The stage: streams q [B, T, heads x stride x d] (a head's query its
    first d columns of ``stride`` x d) and k [B, T, kv_heads x d] with the
    norms' weights laid on every head's lanes a batch row, ``wq`` [B, 1,
    heads x d] and ``wk`` [B, 1, kv_heads x d] (so they cut as the streams
    do, and their gradient leaves a row and head at a time) -> q [B, heads,
    T, d], k [B, kv_heads, T, d] in the streams' dtype: RMSNorm over a
    head's d features in float32 (weight, or 1 + weight where
    ``zero_centered``) and rotate-half RoPE on the first ``rotary_dim`` of
    them (None: none) at positions 0 .. T - 1, rounded once. Differentiable
    in the streams and the weights; what a backward pass keeps is those.
    ``tokens``: what a program takes of a row (a multiple of 16;
    ``QK_TOKENS``; a shorter row is padded to it), ``lanes``: of the q
    stream (``QK_LANES``)."""
    heads, kv_heads = wq.shape[2] // d, wk.shape[2] // d
    if not supported(heads, kv_heads, d, q.dtype, rotary_dim):
        raise ValueError(
            f"the kernels take no {heads} / {kv_heads} heads of {d} in "
            f"{q.dtype} rotated over {rotary_dim}")
    t = q.shape[1]
    tokens = tokens or QK_TOKENS
    pad = -t % tokens
    if pad:
        q, k = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k))
    static = (("d", int(d)), ("stride", int(stride)),
              ("steps", qk_steps(heads, kv_heads, d, stride, lanes)),
              ("tokens", int(tokens)),
              ("rotary_dim", rotary_dim and int(rotary_dim)),
              ("base", float(base)), ("eps", float(eps)),
              ("zero_centered", bool(zero_centered)),
              ("interpret", bool(interpret)))
    outs = _qk(q, k, wq, wk, static)
    return tuple(o[:, :, :t] for o in outs) if pad else outs
