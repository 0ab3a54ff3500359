"""The expert layer's choice — which k of E experts a token takes, the
scores at them, how many tokens took each expert — as two Pallas TPU kernels
under a ``jax.custom_vjp``: ``moe_route_fwd`` and ``moe_route_bwd``.
``incubate.moe._route`` runs the same function in XLA operations
(``jax.lax.top_k``, a sort of every row with its index payload, and masked
sums over ``[N, k, E]`` one-hots), and is the kernels' reference.

A program takes ``ROUTE_TOKENS`` rows of the ``[N, E]`` float32 arrays and
turns them in VMEM, so that the TOKENS lie on the lanes and the experts on
the sublanes, ``[E, tokens]``: the maximum over a token's experts is then an
element-wise maximum across E / 8 vregs and one sublane reduction for 128
tokens at a time, and ``topi`` / ``topv`` leave as lane-dense ``[k, tokens]``
rows (with the experts on the lanes every round would pay two lane
reductions a row group, and the ``[tokens, k]`` results would fill k of 128
lanes). k rounds of: the maximum, the LOWEST index that holds it, the score
at that index, the entry set to -inf. No sort, nothing ``[N, k, E]``-shaped.
A token's lane never meets another's, so a row of padding stays padding.

The count is the tile's chosen mask (the entries the rounds set to -inf)
summed over its lane groups into an ``[E, 128]`` block that stays in VMEM
over the whole grid (one block index: the grid runs in order); the 128
lanes are summed outside.

``moe_route_bwd`` is the gather's transpose without a scatter: ``d
scores[n, e] = sum_j [topi[n, j] == e] d topv[n, j]`` as k selects on the
same ``[E, tokens]`` tile, turned back and written as ``[tokens, E]``. What
a differentiated program keeps is ``topi``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
LANES = 128
#: tokens a program takes (lanes of the turned tile): 128 to 1,024 read
#: within 0.05 ms of each other a call at the cells' shapes, 512 the best
#: all round (tools/route_bench.py; PERF.md section 6, PR 52)
ROUTE_TOKENS = 512
#: most experts a tile holds: two [E, tokens] float32 copies beside the
#: double-buffered blocks
ROUTE_EXPERTS = 2048

# the count's block is revisited by every program: the grid runs in order
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=64 * 2**20)


def supported(experts, k):
    """Whether the kernels take this choice: k of at most ``ROUTE_EXPERTS``
    experts (any count: the arrays are padded to whole lane groups with
    experts that are never chosen)."""
    return 0 < k <= experts <= ROUTE_EXPERTS


def _fwd_kernel(*refs, k, biased, valid):
    """refs: select [tokens, E] (, scores [tokens, E]) -> topv, topi
    [k, tokens], counts [E, 128]; scratch: the turned tile(s)."""
    if biased:
        sel_ref, sc_ref, topv_ref, topi_ref, ne_ref, x_scr, s_scr = refs
        s_scr[...] = sc_ref[...].T
    else:
        sel_ref, topv_ref, topi_ref, ne_ref, x_scr = refs
    x_scr[...] = sel_ref[...].T
    e, tokens = x_scr.shape
    expert = jax.lax.broadcasted_iota(jnp.int32, (e, tokens), 0)

    def one_round(j, carry):
        x = x_scr[...]
        top = jnp.max(x, axis=0, keepdims=True)
        # ties go to the lower index, as jax.lax.top_k's do
        at = jnp.min(jnp.where(x == top, expert, e), axis=0, keepdims=True)
        first = expert == at
        topi_ref[pl.ds(j, 1), :] = at
        # the score AT the index: the maximum itself where the choice is
        # made on the scores, else a masked sum of one entry (exact)
        topv_ref[pl.ds(j, 1), :] = (
            jnp.sum(jnp.where(first, s_scr[...], 0.0), axis=0, keepdims=True)
            if biased else top)
        x_scr[...] = jnp.where(first, -jnp.inf, x)
        return carry

    jax.lax.fori_loop(0, k, one_round, 0)
    chosen = x_scr[...] == -jnp.inf
    if valid is not None:    # the last tile's rows of padding choose too
        token = (jax.lax.broadcasted_iota(jnp.int32, (e, tokens), 1)
                 + pl.program_id(0) * tokens)
        chosen = chosen & (token < valid)
    chosen = chosen.astype(_F32)
    part = chosen[:, :LANES]
    for g in range(1, tokens // LANES):
        part = part + chosen[:, g * LANES:(g + 1) * LANES]

    @pl.when(pl.program_id(0) == 0)
    def _():
        ne_ref[...] = part

    @pl.when(pl.program_id(0) > 0)
    def _():
        ne_ref[...] += part


def _bwd_kernel(topi_ref, dv_ref, ds_ref, acc_scr, *, k):
    """topi, d topv [k, tokens] -> d scores [tokens, E]."""
    e, tokens = acc_scr.shape
    expert = jax.lax.broadcasted_iota(jnp.int32, (e, tokens), 0)
    acc_scr[...] = jnp.zeros((e, tokens), _F32)

    def one_round(j, carry):
        # an expert is a token's choice once: a select, not a sum
        acc_scr[...] = jnp.where(expert == topi_ref[pl.ds(j, 1), :],
                                 dv_ref[pl.ds(j, 1), :], acc_scr[...])
        return carry

    jax.lax.fori_loop(0, k, one_round, 0)
    ds_ref[...] = acc_scr[...].T


def _rows(tokens, width):
    return pl.BlockSpec((tokens, width), lambda i: (i, 0))


def _cols(k, tokens):
    return pl.BlockSpec((k, tokens), lambda i: (0, i))


# jitted, as the other stages' calls are: a layer's call sites (forward,
# recomputed forward, backward, in every layer) share ONE trace and one
# lowering of the kernel's body a shape
@functools.partial(jax.jit, static_argnames=("k", "tokens", "valid",
                                             "interpret"))
def _forward(select, scores, *, k, tokens, valid, interpret):
    n, e = select.shape
    biased = scores is not None
    arrays = (select, scores) if biased else (select,)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, biased=biased, valid=valid),
        grid=(n // tokens,),
        in_specs=[_rows(tokens, e)] * len(arrays),
        out_specs=[_cols(k, tokens), _cols(k, tokens),
                   pl.BlockSpec((e, LANES), lambda i: (0, 0))],
        out_shape=[like((k, n), _F32), like((k, n), jnp.int32),
                   like((e, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((e, tokens), _F32)] * len(arrays),
        interpret=interpret, name="moe_route_fwd", compiler_params=_PARAMS,
    )(*arrays)


@functools.partial(jax.jit, static_argnames=("experts", "tokens",
                                             "interpret"))
def _backward(topi, dv, *, experts, tokens, interpret):
    k, n = topi.shape
    return pl.pallas_call(
        functools.partial(_bwd_kernel, k=k),
        grid=(n // tokens,),
        in_specs=[_cols(k, tokens), _cols(k, tokens)],
        out_specs=_rows(tokens, experts),
        out_shape=jax.ShapeDtypeStruct((n, experts), _F32),
        scratch_shapes=[pltpu.VMEM((experts, tokens), _F32)],
        interpret=interpret, name="moe_route_bwd", compiler_params=_PARAMS,
    )(topi, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _choice(select, scores, static):
    """[N, E] padded to whole tiles -> topv, topi [k, N], counts [E, 128];
    ``static``: (k, tokens, valid, interpret, biased) — not ``biased``: the
    weights are read from ``select`` itself and ``scores`` is None."""
    k, tokens, valid, interpret, biased = static
    if biased:
        # the choice is made on select as it stands: no gradient reaches it
        select = jax.lax.stop_gradient(select)
    return tuple(_forward(select, scores, k=k, tokens=tokens, valid=valid,
                          interpret=interpret))


def _choice_fwd(select, scores, static):
    outs = _choice(select, scores, static)
    return outs, outs[1]


def _choice_bwd(static, topi, cotangents):
    _, tokens, _, interpret, biased = static
    d = _backward(topi, cotangents[0], experts=cotangents[2].shape[0],
                  tokens=tokens, interpret=interpret)
    return (None, d) if biased else (d, None)


_choice.defvjp(_choice_fwd, _choice_bwd)


def route_choice(select, scores, k, *, tokens=None, interpret=False):
    """The stage: ``select`` [N, E] float32, what the choice is made on
    (every entry above -inf), and ``scores`` [N, E] float32, what the
    weights are read from (``select`` itself, the same array, where they
    are one) -> ``topv`` [N, k] float32, ``topi`` [N, k] int32, ``n_e`` [E]
    float32. ``topi`` is ``jax.lax.top_k(select, k)[1]`` element for element
    (descending, ties to the lower index), ``topv[n, j]`` is ``scores[n,
    topi[n, j]]`` bit for bit, ``n_e[e]`` the number of (token, choice)
    pairs that chose e. Differentiable in ``scores`` (``d scores[n, e]`` =
    the ``d topv[n, j]`` whose ``topi[n, j]`` is e, else 0); ``select``
    takes no gradient of its own. ``tokens``: what a program takes
    (``ROUTE_TOKENS``; a multiple of 128)."""
    n, e = select.shape
    if not supported(e, k):
        raise ValueError(f"the kernels take no top-{k} of {e} experts")
    tokens = tokens or ROUTE_TOKENS
    biased = scores is not select
    pad_n, pad_e = -n % tokens, -e % LANES

    def padded(a, fill):
        a = a.astype(_F32)
        if pad_e:    # experts no round can choose
            a = jnp.pad(a, ((0, 0), (0, pad_e)), constant_values=fill)
        return jnp.pad(a, ((0, pad_n), (0, 0))) if pad_n else a

    static = (int(k), int(tokens), n if pad_n else None, bool(interpret),
              biased)
    topv, topi, counts = _choice(
        padded(select, -jnp.inf), padded(scores, 0.0) if biased else None,
        static)
    return (topv.T[:n], topi.T[:n], jnp.sum(counts, axis=1)[:e])
