"""The selective state-space scan (Mamba-2's SSD; ``ops/linear_attention.py``
has the equations) as two Pallas TPU kernels under a ``jax.custom_vjp``:
``ssd_chunk_fwd`` and ``ssd_chunk_bwd``. The scalar-decay scan with NO delta
correction — no inverse, nothing sequential inside a chunk but the state —
so bodies of its own beside the delta rule's (``linear_attention.py`` here,
whose small helpers it shares and none of which it changes).

A program is one batch row, ``together`` heads side by side in the lanes of
its blocks and one block of ``tokens`` tokens, taken from the layer's arrays
as the convolution kernels leave them: x and y ``[B, T, H P]`` streams (block
``(1, tokens, heads x P)``), B and C ``[B, T, G N]`` streams (block ``(1,
tokens, N)`` of the heads' GROUP: one block the program's heads share, never
a copy a head). The step ``dt`` and the decay's logarithm ``g = dt A`` come a
row a head, ``[B, H, T]`` float32 (block ``(1, heads, tokens)``: what the
delta rule's kernels do with their beta), the skip ``D`` a number a lane,
``[B, 1, H P]``. The head view ``[.., H, P]`` never exists in HBM. The block
axis is the grid's last, ``arbitrary``: the heads' states ``[N, heads x P]``
— rows d_state, the heads' P side by side in the lanes — live in float32
VMEM scratch across it, zero at a row's first block.

A chunk of ``chunk`` tokens, in VMEM and registers only:

- ``G`` = g summed from the chunk's first token, every head at once (a
  product with the triangle of ones, float32 in earnest: three bf16 passes
  over g split exactly in three), as rows ``[heads, C]``, and laid on the
  heads' lanes ``[C, heads x P]`` with dt by a product with the 0/1 matrix
  of which lane is whose;
- the GROUP's pair product ``C B^T``, one ``[C, N] x [N, C]`` for all the
  program's heads; a head's ``[C, C]`` mask ``exp(G_r - G_i)`` on the
  triangle i <= r — off it the exponent is held at a large negative number,
  so no exponent is positive and the term is 0 —; the head's masked product
  with ``dt x``. **Values of 64 on 128 lanes**: a head's x is half a lane
  group, and a ``[C, C] x [C, 64]`` product fills half of the MXU's columns
  whatever is done, so each head of a lane group takes a full-width product
  with the group's ``[C, 128]`` block and the lanes that are its own are
  picked from it: no lane shuffle, no more MXU passes (the backward holds
  the other heads' lanes of ONE operand at zero where a product contracts
  over them);
- the state's read ``exp(G) (C S)`` and update ``S <- exp(G_C) S + B^T (dt x
  exp(G_C - G))``: ONE product each for all the program's heads, full lane
  width, because C and B are the group's; the ``D`` skip added and y
  rounded to x's dtype once.

Precision is ``ssd_chunked``'s: g's sums, the mask, the state and y before
its one rounding are float32; the large products take operands of x's dtype
and accumulate in float32.

The forward under differentiation also writes each chunk's ENTERING state
(float32 ``[N, heads x P]``: 32 KB a chunk and head of 64 on a state of 128),
alive from a block's recomputed forward to its backward. The backward walks
blocks and chunks in reverse with ``dS`` in VMEM scratch, rebuilds G, the
mask and the pair product from the inputs, and emits dx (x's dtype), d dt
and dg (float32 rows; the wrapper's plain ``g = dt A`` outside gives dt its
second route and A its gradient), dB and dC (summed over the program's heads
in VMEM, over programs as float32 partial sums XLA adds) and dD a lane
(float32, summed over a row's blocks in VMEM). The mask's part of the decay's
gradient is a row sum minus a column sum of (cotangent x term).

``interpret=True`` runs both in the Pallas interpreter (the CPU tests and
the chip_smoke dry run ask for it; never inferred from the backend).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .linear_attention import (_F32, _HIGHEST, _NN, _NT, _PARAMS, _TN, _dot,
                               _in_three, _iota)

#: tokens a chunk, a program, and lanes of x (heads x P) a program takes:
#: a v5e reading at Granite-4.0-H's shape (tools/ssd_bench.py --path, ms
#: forward / forward + backward, PR 50: 128 x 256 x 1024 0.646 / 1.908,
#: 128 x 512 x 1024 0.600 / 1.841, 64 x 256 x 1024 0.730 / 2.227, 256 x 256
#: x 1024 0.656 / 2.103, 128 x 256 x 512 0.715 / 2.076, 128 x 256 x 2048
#: 0.554 / 1.780 at twice the compile; the XLA scan on the same streams
#: 2.156 / 6.881)
CHUNK, TOKENS, LANES = 128, 256, 1024
#: what an exponent is held at off the triangle: its exponential is 0
_MASKED = -1e30


def heads_together(heads, groups, d_head, lanes=None):
    """Heads a program takes: the most whose x fills up to ``lanes``
    (``LANES``) lanes, that divide a group's heads (B and C are one block a
    program), fill whole lane groups and are whole 8-row tiles of the
    ``[B, H, T]`` rows — or all the heads. 0 where no cut serves."""
    per_group = heads // groups
    most = max(1, (lanes or LANES) // d_head)
    return next((n for n in range(min(most, per_group), 0, -1)
                 if per_group % n == 0 and (n == heads or (
                     n % 8 == 0 and n * d_head % 128 == 0))), 0)


def supported(heads, groups, d_head, d_state, dtype):
    """Whether the kernels take this scan: a state that fills whole lane
    groups, values that divide or are a multiple of the 128 lanes, a head
    cut (``heads_together``) and bf16 operands (float32 operands run in the
    interpreter, which the tests use; a program's float32 scan is the XLA
    path's)."""
    return (d_state % 128 == 0 and heads % groups == 0
            and (128 % d_head == 0 or d_head % 128 == 0)
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and heads_together(heads, groups, d_head) > 0)


def block_tokens(seq, chunk=None, tokens=None):
    """Tokens a program takes: ``tokens`` (``TOKENS``: whole chunks, whole
    lane groups of the ``[B, H, T]`` rows) or, for a shorter row, the row in
    whole chunks."""
    chunk = chunk or CHUNK
    return min(tokens or TOKENS, -(-seq // chunk) * chunk)


def _head_lanes(heads, lanes):
    """[heads, lanes] bf16: 1 where a lane is the head's."""
    return (_iota((heads, lanes), 1) // (lanes // heads)
            == _iota((heads, lanes), 0)).astype(jnp.bfloat16)


def _on_lanes(rows, expand):
    """A number a head and token [heads, C] on the head's lanes [C, heads x
    P], float32 in earnest: a 0 or 1 multiplies each of the three bf16 terms
    exactly."""
    return sum(_dot(term, expand, _TN) for term in _in_three(rows))


def _head_rows(x, expand):
    """The sum over each head's lanes, a row a head: [C, heads x P] ->
    [heads, C], float32 in earnest."""
    return sum(_dot(expand, term, _NT) for term in _in_three(x))


def _spans(heads, width):
    """The lane spans the per-head products run on: (lanes, [(head, its
    place among the span's heads)]) — a head's own lanes where they are
    whole lane groups, else the 128-lane group it shares."""
    if width >= 128:
        return [(slice(h * width, (h + 1) * width), [(h, None)])
                for h in range(heads)]
    share = 128 // width
    return [(slice(j * 128, (j + 1) * 128),
             [(j * share + k, k) for k in range(share)])
            for j in range(heads * width // 128)]


def _chunk_terms(x, dt, g, b, c, heads):
    """What both passes build of a chunk from its inputs: x [C, L] (L =
    heads x P) and b, c [C, N] in the operand dtype, dt and g [heads, C]
    float32. A dict: ``xf``, ``dt_l`` (dt on the lanes), ``v`` = dt x, ``cum``
    (G as rows [heads, C]) and ``cum_l`` (on the lanes), ``e_in`` = exp(G),
    ``e_out`` = exp(G_C - G), ``dec`` = exp(G_C) [1, L], ``u`` = v e_out,
    ``pairs`` = C B^T [C, C], ``expand``."""
    size, lanes = x.shape
    expand = _head_lanes(heads, lanes)
    upper = (_iota((size, size), 0) <= _iota((size, size), 1)).astype(
        jnp.bfloat16)
    cum = sum(_dot(term, upper, _NN) for term in _in_three(g))
    cum_l = _on_lanes(cum, expand)
    dt_l = _on_lanes(dt, expand)
    last_l = cum_l[size - 1:size]
    xf = x.astype(_F32)
    v = xf * dt_l
    e_out = jnp.exp(last_l - cum_l)
    return dict(xf=xf, dt_l=dt_l, v=v, cum=cum, cum_l=cum_l,
                e_in=jnp.exp(cum_l), e_out=e_out, dec=jnp.exp(last_l),
                u=v * e_out, pairs=_dot(c, b, _NT, _HIGHEST), expand=expand)


def _mask(terms, head, width):
    """A head's [C, C] mask of exponentials: exp(G_r - G_i) where i <= r,
    0 elsewhere; no exponent is positive."""
    size = terms["cum"].shape[1]
    r, i = _iota((size, size), 0), _iota((size, size), 1)
    column = terms["cum_l"][:, head * width:head * width + 1]
    row = terms["cum"][head:head + 1]
    return jnp.exp(jnp.where(i <= r, column - row, _MASKED))


def _own(place, width, shape):
    """Whether a lane of a shared 128-lane span is the ``place``-th head's."""
    return _iota(shape, 1) // width == place


def _fwd_kernel(x_ref, dt_ref, g_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                chunk, heads, keep):
    if keep:
        s0_ref, s_ref = rest
    else:
        (s_ref,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        s_ref[...] = jnp.zeros(s_ref.shape, _F32)

    tokens, lanes = x_ref.shape[1:]
    width, mm = lanes // heads, x_ref.dtype
    d_l = d_ref[0]
    for n in range(tokens // chunk):
        at = slice(n * chunk, (n + 1) * chunk)
        b, c = b_ref[0, at], c_ref[0, at]
        t = _chunk_terms(x_ref[0, at], dt_ref[0, :, at], g_ref[0, :, at], b,
                         c, heads)
        state = s_ref[...]
        if keep:
            s0_ref[0, 0, n] = state
        vb = t["v"].astype(mm)
        read = t["e_in"] * _dot(c, state.astype(mm), _NN) + d_l * t["xf"]
        s_ref[...] = t["dec"] * state + _dot(b, t["u"].astype(mm), _TN)
        for span, owners in _spans(heads, width):
            y = None
            for head, place in owners:
                w = (t["pairs"] * _mask(t, head, width)).astype(mm)
                full = _dot(w, vb[:, span], _NN)
                y = full if y is None else jnp.where(
                    _own(place, width, full.shape), full, y)
            y_ref[0, at, span] = (read[:, span] + y).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, g_ref, b_ref, c_ref, d_ref, s0_ref, dy_ref,
                dx_ref, ddt_ref, dg_ref, db_ref, dc_ref, dd_ref, ds_ref, *,
                chunk, heads):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, _F32)

    tokens, lanes = x_ref.shape[1:]
    width, mm = lanes // heads, x_ref.dtype
    d_l = d_ref[0]
    r, i = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    later = (r >= i).astype(jnp.bfloat16)
    for n in reversed(range(tokens // chunk)):
        at = slice(n * chunk, (n + 1) * chunk)
        b, c = b_ref[0, at], c_ref[0, at]
        t = _chunk_terms(x_ref[0, at], dt_ref[0, :, at], g_ref[0, :, at], b,
                         c, heads)
        expand, e_in, e_out, u = t["expand"], t["e_in"], t["e_out"], t["u"]
        s0 = s0_ref[0, 0, n]
        s0m = s0.astype(mm)
        ds1 = ds_ref[...]
        ds1m = ds1.astype(mm)
        dym = dy_ref[0, at]
        dyf = dym.astype(_F32)
        vb = t["v"].astype(mm)
        # the state's read y = e_in (C S0) and its update S1 = dec S0 + B^T u
        read = _dot(c, s0m, _NN)
        dread = (dyf * e_in).astype(mm)
        du = _dot(b, ds1m, _NN)
        ds_ref[...] = t["dec"] * ds1 + _dot(c, dread, _TN)
        dc = _dot(dread, s0m, _NT)
        db = _dot(u.astype(mm), ds1m, _NT)
        dv = du * e_out
        # the decay's cotangent, a lane: through e_in, e_out and, at the
        # chunk's last token, the chunk's whole decay
        dlast = (jnp.sum(du * u, axis=0, keepdims=True)
                 + jnp.sum(ds1 * s0, axis=0, keepdims=True) * t["dec"])
        dcum_l = dyf * read * e_in - du * u + jnp.where(
            _iota((chunk, lanes), 0) == chunk - 1, dlast, 0.0)
        # the heads' masked products: y_h = (pairs * mask_h) v_h
        dpairs = jnp.zeros((chunk, chunk), _F32)
        dcum = jnp.zeros((heads, chunk), _F32)
        dvs = []
        for span, owners in _spans(heads, width):
            dv_span = None
            for head, place in owners:
                mask = _mask(t, head, width)
                w = t["pairs"] * mask
                shape = (chunk, span.stop - span.start)
                mine = None if place is None else _own(place, width, shape)
                dy_h = dym[:, span] if mine is None else jnp.where(
                    mine, dym[:, span], jnp.zeros(shape, mm))
                dw = _dot(dy_h, vb[:, span], _NT)
                full = _dot(w.astype(mm), dym[:, span], _TN)
                dv_span = full if dv_span is None else jnp.where(
                    mine, full, dv_span)
                dpairs = dpairs + dw * mask
                moved = dw * w
                along = (jnp.sum(jnp.where(r == i, jnp.sum(
                    moved, axis=1, keepdims=True), 0.0), axis=0,
                    keepdims=True) - jnp.sum(moved, axis=0, keepdims=True))
                dcum = jnp.where(_iota(dcum.shape, 0) == head, along, dcum)
            dvs.append(dv_span)
        dv = dv + jnp.concatenate(dvs, axis=1)
        dpm = dpairs.astype(mm)
        dc_ref[0, 0, at] = dc + _dot(dpm, b, _NN)
        db_ref[0, 0, at] = db + _dot(dpm, c, _TN)
        dx_ref[0, at] = (dv * t["dt_l"] + d_l * dyf).astype(dx_ref.dtype)
        dd_ref[0] += jnp.sum(dyf * t["xf"], axis=0, keepdims=True)
        ddt_ref[0, :, at] = _head_rows(dv * t["xf"], expand)
        # every later token of the chunk carries this token's decay
        dcum = dcum + _head_rows(dcum_l, expand)
        dg_ref[0, :, at] = sum(_dot(term, later, _NN)
                               for term in _in_three(dcum))


_STATIC = ("chunk", "tokens", "together", "groups", "interpret")


def _specs(tokens, chunk, together, groups, heads, d_state, lanes, at):
    """Block specs of a [B, T, H P] stream, the [B, H, T] rows, a [B, T,
    G N] stream of the heads' group, the [B, 1, H P] skip, the [B, H /
    together, T / C, N, together x P] entering states and the [B, programs a
    group, T, G N] partial sums of dB and dC; ``at`` maps the grid's block
    axis to the block taken."""
    a_group = heads // groups // together        # programs a group
    stream = pl.BlockSpec((1, tokens, lanes), lambda b, h, n: (b, at(n), h))
    rows = pl.BlockSpec((1, together, tokens), lambda b, h, n: (b, h, at(n)))
    group = pl.BlockSpec((1, tokens, d_state),
                         lambda b, h, n: (b, at(n), h // a_group))
    skip = pl.BlockSpec((1, 1, lanes), lambda b, h, n: (b, 0, h))
    states = pl.BlockSpec((1, 1, tokens // chunk, d_state, lanes),
                          lambda b, h, n: (b, h, at(n), 0, 0))
    partial = pl.BlockSpec(
        (1, 1, tokens, d_state),
        lambda b, h, n: (b, h % a_group, at(n), h // a_group))
    return stream, rows, group, skip, states, partial


# jitted, as the convolution stage's calls are: a layer's call sites
# (forward, recomputed forward, backward, in every layer) share ONE trace
# and one lowering of a body a shape
@functools.partial(jax.jit, static_argnames=_STATIC + ("keep",))
def _forward(x, dt, g, b, c, d, *, chunk, tokens, together, groups, keep,
             interpret):
    bsz, t, hp = x.shape
    heads, d_state = dt.shape[1], b.shape[2] // groups
    lanes = hp // heads * together
    stream, rows, group, skip, states, _ = _specs(
        tokens, chunk, together, groups, heads, d_state, lanes, lambda n: n)
    out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)], [stream]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, heads // together, t // chunk, d_state, lanes), _F32))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, heads=together,
                          keep=keep),
        grid=(bsz, heads // together, t // tokens),
        in_specs=[stream, rows, rows, group, group, skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d_state, lanes), _F32)],
        interpret=interpret, name="ssd_chunk_fwd", compiler_params=_PARAMS,
    )(x, dt, g, b, c, d)
    return out if keep else out[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(x, dt, g, b, c, d, s0, dy, *, chunk, tokens, together, groups,
              interpret):
    bsz, t, hp = x.shape
    heads, d_state = dt.shape[1], b.shape[2] // groups
    lanes = hp // heads * together
    blocks = t // tokens
    stream, rows, group, skip, states, partial = _specs(
        tokens, chunk, together, groups, heads, d_state, lanes,
        lambda n: blocks - 1 - n)
    like = jax.ShapeDtypeStruct
    sums = like((bsz, heads // groups // together, t, b.shape[2]), _F32)
    dx, ddt, dg, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, heads=together),
        grid=(bsz, heads // together, blocks),
        in_specs=[stream, rows, rows, group, group, skip, states, stream],
        out_specs=[stream, rows, rows, partial, partial, skip],
        out_shape=[like(x.shape, x.dtype), like(dt.shape, _F32),
                   like(g.shape, _F32), sums, sums, like(d.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((d_state, lanes), _F32)],
        interpret=interpret, name="ssd_chunk_bwd", compiler_params=_PARAMS,
    )(x, dt, g, b, c, d, s0, dy)
    return (dx, ddt, dg, jnp.sum(db, axis=1).astype(b.dtype),
            jnp.sum(dc, axis=1).astype(c.dtype), dd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, g, b, c, d, static):
    return _forward(x, dt, g, b, c, d, keep=False, **dict(static))


def _scan_fwd(x, dt, g, b, c, d, static):
    y, s0 = _forward(x, dt, g, b, c, d, keep=True, **dict(static))
    return y, (x, dt, g, b, c, d, s0)


def _scan_bwd(static, res, dy):
    return _backward(*res, dy, **dict(static))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, b, c, d, *, groups=1, chunk=None, tokens=None, lanes=None,
        interpret=False):
    """The selective state-space scan from a zero state, on streams: x [B,
    T, H P], dt [B, T, H] (the step after its softplus), b and c [B, T,
    G N] of ``groups`` groups, a and d [H] (or a copy a batch row, [B, H]:
    how ``placement.on_mesh`` shards them with the rows) -> y [B, T, H P] in
    x's dtype (the final state stays inside). b and c take x's dtype, dt, a
    and d are float32; differentiable in all six. ``chunk``: tokens a chunk
    (``CHUNK``), ``tokens``: what a program takes of a row (whole chunks;
    ``TOKENS``), ``lanes``: of x's lanes, in whole heads (``LANES``). A row
    that is no whole number of blocks is padded with tokens of step 0,
    which write nothing and decay nothing."""
    bsz, t, hp = x.shape
    heads = dt.shape[-1]
    chunk = int(chunk or CHUNK)
    together = heads_together(heads, groups, hp // heads, lanes)
    if not together:
        raise ValueError(f"no head cut serves {heads} heads of "
                         f"{hp // heads} in {groups} groups")
    tokens = block_tokens(t, chunk, tokens)
    pad = -t % tokens
    dt = dt.astype(_F32)

    def stream(v):
        v = v.astype(x.dtype)
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    def rows(v):
        return jnp.pad(jnp.swapaxes(v, 1, 2), ((0, 0), (0, 0), (0, pad)))

    skip = jnp.broadcast_to(
        jnp.repeat(d.astype(_F32), hp // heads, axis=-1)[..., None, :],
        (bsz, 1, hp))
    static = (("chunk", chunk), ("tokens", int(tokens)),
              ("together", together), ("groups", int(groups)),
              ("interpret", bool(interpret)))
    y = _scan(stream(x), rows(dt), rows(dt * a.astype(_F32)[..., None, :]),
              stream(b), stream(c), skip, static)
    return y[:, :t] if pad else y
