"""Mixture-of-Experts: a top-k routed expert FFN block, on one device or
with the experts sharded over the 'ep' mesh axis.

The reference (~v2.1) predates its MoE work, so this is green-field
TPU-native design (like ring attention). Expert weights are stacked
[E, ...] (``w_up``, ``w_down``, and ``w_gate`` for SwiGLU experts) and
carry ``mp_spec = P('ep')``. One API, five paths; ``dispatch_mode='auto'``
(the default) picks from what the layer can observe — the number of
experts and whether the program's mesh shards them:

- ``sorted`` (dropless; what ``auto`` resolves to for 8 experts or more
  where no 'ep' axis shards them — one device, or a dp / mp mesh): the
  (token, choice) pairs are sorted by expert, the rows gathered into that
  order, the experts run as grouped matrix multiplications over the ragged
  groups (each row meets its own expert's weights and no other: the Pallas
  megablox kernel on a one-device TPU program, ``jax.lax.ragged_dot``
  elsewhere — ``_grouped_matmul``), and the results are weighted,
  un-sorted by the inverse permutation and summed over the k choices. No [N, E, C] tensor, no
  capacity, no dropped token; exact under any imbalance (an expert with no
  token, an expert with every token). Both permutations are gathers with
  gathers for gradients. It cannot be asked for by name: it is not an
  option, it is what the layer does where nothing is sharded.
- ``dense`` (``auto`` below 8 experts): every expert's FFN runs for every
  token and the top-k gate mask zeroes the rest; the expert-dim contraction
  compiles to a psum over the ep axis. Never drops, static shapes, E/k
  wasted FLOPs.
- ``capacity`` (GShard/Switch; ``auto`` for 8 experts or more under a mesh
  whose 'ep' axis is larger than 1): each expert processes at most
  C = ceil(capacity_factor * k * N / E) tokens; tokens claim capacity slots
  in order (per-expert cumsum) and overflow tokens DROP that expert's
  contribution, exactly the GShard top-2 formulation. Dispatch and combine
  are one-hot [N, E, C] einsums — static shapes, but 10.7 GB in f32 at
  N = 16,384, E = 64, C = 2,560: sizes for tests and small expert counts.
  The [E, C, H] expert buffers inherit the 'ep' sharding from the weights,
  so XLA materialises the token->expert shuffle as collectives over ep.
- ``alltoall`` (explicit only): the literal GShard layout under
  ``jax.shard_map`` — tokens batch-sharded over the data axes x ep, each
  shard routes its LOCAL tokens into [E, C, H] capacity buffers,
  ``lax.all_to_all`` swaps the expert dim across shards, the FFN runs on
  local expert weights only, and a second all_to_all routes results back.
  It requires a live global mesh with ep > 1, batch divisible by ep, and E
  divisible by ep.

- ``sorted_held`` (what a layer built with ``held=(first, count)`` runs):
  one chip's share of an expert-parallel deployment, without the exchange.
  The router scores and picks over ALL experts; only the (token, choice)
  pairs whose expert is one of the ``count`` held from ``first`` on are
  placed, gathered and multiplied, into a row buffer of
  ``held_rows_factor`` times the mean number of such pairs (rounded up to
  the row tile); the layer returns the held experts' part of the sum
  (plus the shared expert). Whatever is as wide as the hidden size is done
  on the computed rows: ``rows`` rows of ``x`` are gathered into expert
  order, and the rows are summed into tokens (the weighted sum over a
  token's choices, and the dispatch gather's gradient) by putting them in
  token order, adding each to its same-token neighbours and gathering N —
  ``rows`` + N gathered rows, in float32, where the N*k pairs would be
  4 to 11 times the rows. The row bound is the reference's
  (``benchmark/references/*.py experts()``): held pairs in (expert, token,
  choice) order; a pair whose rank reaches ``rows`` is dropped. It is
  exact whenever the pairs fit, and the pairs that do not are dropped AND
  counted in the ``held_overflow`` buffer, which leaves a train step with
  the other buffers. Nothing stands in for the absent experts or their
  traffic.

The layer's arithmetic is the constructor's: ``activation`` ('gelu': two
matrices; 'relu2': two matrices, ``w_down(relu(x w_up)^2)``, Nemotron-H's;
'swiglu': ``w_down(silu(x w_gate) * (x w_up))``), ``latent_size``
(LatentMoE, Nemotron 3: the routed experts live in a latent space — one
shared ``latent_down`` hidden -> latent before the dispatch, experts latent
-> ffn -> latent, one shared ``latent_up`` after the combine; the router
and the shared expert stay on the hidden-wide stream; scopes
``latentmoe.down`` / ``latentmoe.up``), ``gate_bias``,
``norm_topk_prob`` (renormalise the k weights or keep the router's own),
``scoring`` ('softmax'; 'sigmoid' with ``select_bias``, a buffer added to
the scores for the CHOICE only and moved by ``bias_update_speed`` against
each training step's loads — DeepSeek-V3's auxiliary-loss-free balancing —
and ``routed_scale``), ``shared_width`` (an expert every token takes, added
to the routed sum: relu² on two matrices where the routed experts are
'relu2', else a SwiGLU — with ``shared_gate`` times ``sigmoid(x w_s)`` a token,
Qwen3-Next's; sigmoid scoring, the shared expert, relu² and the latent
space run on the two sorted paths), and two auxiliary losses through ``nn.aux_loss.emit_aux_loss``: load balancing
times ``aux_weight`` and the router z-loss ``mean_t logsumexp(r_t)^2`` times
``z_loss_weight``. The sorted path's load-balancing term is the
Switch / HF form over all k choices, ``E * sum_e (n_e / N) * mean_t
p[t, e]`` (n_e = assignments to e); dense divides it by k; capacity and
alltoall use GShard's top-1 fraction.

The sorted paths' router (``_route``, scope ``moe.route``) is a float32
product, the scoring, and THE CHOICE (``_choice``): the k ids of a token in
``jax.lax.top_k``'s order, the scores at them, and n_e — made once: the
balancing term and the selection bias's update read the same count. The
choice has two implementations and no knob (``route_path``, asked by
``route_kernel`` outside the dispatched op, as the grouped matmul's
``placement.kernel(sharded=False)`` beside it): the Mosaic stage
``ops/pallas/moe_route.py`` — tokens on the lanes, k rounds of arg-max in
VMEM, one call a pass, the gradient k selects — where the program may hold
kernels, the tokens fill a tile and the experts are 64 or more; else XLA's
sort of every row and sums over [N, k, E] one-hots (a CPU, a mesh of several
devices, 32 experts). Same ids, same weights, bit for bit.

Which path a call took is counted in
``paddle_tpu_moe_dispatch_total{path}``, which arithmetic in
``paddle_tpu_moe_layer_total{activation, latent}``, how the router's choice
ran in ``paddle_tpu_moe_route_total{path}`` (``kernel`` | ``xla``) — trace
time: one a layer call.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import functools

from .. import nn
from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..obs import metrics as obs_metrics
from ..ops import placement
from jax import shard_map

_DISPATCH_TOTAL = obs_metrics.counter(
    "paddle_tpu_moe_dispatch_total",
    "expert-layer calls by the path taken (sorted | sorted_held | capacity "
    "| dense | alltoall); under jit one count per traced layer call",
    labelnames=("path",))

_LAYER_TOTAL = obs_metrics.counter(
    "paddle_tpu_moe_layer_total",
    "expert-layer calls by the experts' activation (gelu | relu2 | swiglu) "
    "and the width of the latent space they live in (0: the hidden "
    "stream's own); under jit one count per traced layer call",
    labelnames=("activation", "latent"))

_ROUTE_TOTAL = obs_metrics.counter(
    "paddle_tpu_moe_route_total",
    "routers of the sorted expert paths by how their choice (top-k ids, "
    "the scores at them, the loads) ran: kernel (one Mosaic call a pass) | "
    "xla (a sort and one-hot sums); one count per traced layer call",
    labelnames=("path",))

#: ``auto`` runs every expert on every token below this many experts
_DENSE_BELOW = 8

#: the router's choice runs as the Mosaic stage from this many experts on:
#: the tile holds whole lane groups of 128, so 64 experts run the rounds on
#: twice their vregs and still beat XLA's sort (0.084 against 0.126 ms a
#: forward call at OLMoE's 16,384 tokens, and 0.116 against 1.021 with the
#: backward, whose XLA form is top_k's scatter), 32 on four times theirs and
#: do not (0.119 against 0.107 at LFM2's 32,768; tools/route_bench.py on
#: the v5e, PERF.md section 6, PR 52)
_ROUTE_KERNEL_FROM = 64


def _expert_ffn(buf, w_gate, w_up, w_down, eq_up, eq_down):
    """The experts' FFN as batched einsums over stacked weights: gelu on
    two matrices, or SwiGLU where the layer holds ``w_gate``."""
    h = jnp.einsum(eq_up, buf, w_up)
    if w_gate is None:
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.silu(jnp.einsum(eq_up, buf, w_gate)) * h
    return jnp.einsum(eq_down, h, w_down)


def _relu2(x):
    """Nemotron-H's activation: ``relu(x)^2``."""
    return jnp.square(jax.nn.relu(x))


def _z_loss(logits):
    """Router z-loss (ST-MoE): mean over tokens of logsumexp(logits)^2."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(lse))


# ---------------------------------------------------------------- sorted
# The dropless path's two permutations. Both are bijections on the N*k
# (token, choice) pairs, so each is a row gather whose gradient is the row
# gather by the inverse permutation: no scatter in either direction.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_expert_order(x, order, inv, k):
    """x [N, H] -> [N*k, H]: row i is the token of sorted pair i."""
    return x[order // k]


def _rows_to_expert_order_fwd(x, order, inv, k):
    return x[order // k], inv


def _rows_to_expert_order_bwd(k, inv, g):
    by_token = g[inv].reshape(inv.shape[0] // k, k, g.shape[-1])
    return (jnp.sum(by_token.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_rows_to_expert_order.defvjp(_rows_to_expert_order_fwd,
                             _rows_to_expert_order_bwd)


@jax.custom_vjp
def _rows_to_token_order(ys, order, inv):
    """ys [N*k, H] in expert order -> token-major order (pair t*k + j)."""
    return ys[inv]


def _rows_to_token_order_fwd(ys, order, inv):
    return ys[inv], order


def _rows_to_token_order_bwd(order, g):
    return g[order], None, None


_rows_to_token_order.defvjp(_rows_to_token_order_fwd,
                            _rows_to_token_order_bwd)


def route_path(tokens, experts, k):
    """``kernel`` | ``xla`` for the choice of ``k`` of ``experts`` experts
    by ``tokens`` tokens, from what can be observed: the Mosaic kernels
    (``ops/pallas/moe_route.py``) where the program may hold them — the
    expert layer takes no ``shard_map`` of its own, so
    ``placement.kernel(sharded=False)``, as for its grouped matmul —, the
    tokens fill a tile, and the experts are as many as the kernels beat
    XLA's sort at (``_ROUTE_KERNEL_FROM``). The XLA stage everything
    else."""
    from ..ops.pallas import moe_route as kernels

    if (tokens >= kernels.ROUTE_TOKENS and experts >= _ROUTE_KERNEL_FROM
            and kernels.supported(experts, k)
            and placement.kernel(sharded=False)):
        return "kernel"
    return "xla"


def route_kernel(tokens, experts, k):
    """One call's decision, counted, as ``_route`` takes it (``choice=``):
    ``placement.kernel``'s answer where ``route_path`` says ``kernel``, else
    None (the XLA stage). Asked OUTSIDE the dispatched op; the answer rides
    its static arguments."""
    path = route_path(tokens, experts, k)
    _ROUTE_TOTAL.inc(path=path)
    return placement.kernel(sharded=False) if path == "kernel" else None


def _choice(select, scores, k, kernel=None):
    """The router's choice: ``select`` [N, E] float32 (what the choice is
    made on) and ``scores`` [N, E] (what the weights are read from; the
    same array where they are one) -> the scores at the chosen experts
    [N, k], their ids [N, k] (``jax.lax.top_k``'s order: descending, ties to
    the lower index) and the pairs that chose each expert [E]. ``kernel``:
    ``route_kernel``'s answer — the Mosaic stage (one call a pass, k rounds
    of arg-max in VMEM), or None, the XLA stage: a sort of every row, and
    [N, k, E] one-hots for the count and, where the choice is not made on
    the scores themselves, for the scores at the ids (exact in float32: a
    gather of 8 of 256 columns a row took 6.7 ms a step on the v5e at 8,192
    tokens, PERF.md section 6, PR 29, and its gradient would be a
    scatter)."""
    if kernel is not None:
        from ..ops.pallas.moe_route import route_choice

        return route_choice(select, scores, k,
                            interpret=kernel == "interpret")
    e = select.shape[-1]
    if select is scores:
        topv, topi = jax.lax.top_k(scores, k)
    else:
        topi = jax.lax.top_k(select, k)[1]
        topv = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32)
                       * scores[:, None, :], axis=-1)
    n_e = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=(0, 1))
    return topv, topi, n_e


def _route(x, w_router, b_router, select_bias=None, *, top_k, renorm,
           scoring="softmax", routed_scale=1.0, renorm_eps=1e-20,
           choice=None, counts=False):
    """The router in float32: [B, S, H] -> the k weights [N, k] and expert
    ids [N, k] of every token, the load-balancing term (Switch / HF form
    over all k choices) and the z-loss; with ``counts`` also the pairs that
    chose each expert [E], a fifth result.
    ``scoring`` 'softmax': the weights are the softmax's own unless
    ``renorm``. 'sigmoid' (DeepSeek-V3's ``noaux_tc``): s = sigmoid(logits);
    the choice is top-k of s + ``select_bias``, the weights are s at the
    chosen experts (the bias moves the choice and never the weight),
    divided by their sum + ``renorm_eps`` if ``renorm`` (the sources differ
    in what they add: 1e-20, LFM2 1e-6), times ``routed_scale``; the
    balancing term takes s normalised over the experts, and there is no
    z-loss. ``choice``: ``route_kernel``'s answer (``_choice``)."""
    with jax.named_scope("moe.route"):
        xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        # float32 in earnest: a TPU's default precision would multiply in
        # bf16, and a router logit off by 2e-3 changes which experts a
        # token takes (64 columns: the six passes cost nothing)
        logits = jnp.dot(xf, w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if b_router is not None:
            logits = logits + b_router.astype(jnp.float32)
        n, e = logits.shape
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            biased = (scores if select_bias is None else
                      scores + select_bias.astype(jnp.float32))
            topv, topi, assigned = _choice(jax.lax.stop_gradient(biased),
                                           scores, top_k, choice)
            if renorm:
                topv = topv / (jnp.sum(topv, axis=-1, keepdims=True)
                               + renorm_eps)
            topv = topv * routed_scale
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            topv, topi, assigned = _choice(probs, probs, top_k, choice)
            if renorm:
                topv = topv / jnp.maximum(
                    jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
        balance = e * jnp.sum(assigned / n * jnp.mean(probs, axis=0))
        z = (_z_loss(logits) if scoring == "softmax"
             else jnp.zeros((), jnp.float32))
        routed = (topv, topi.astype(jnp.int32), balance, z)
        return routed + (assigned,) if counts else routed


#: megablox tilings (rows, contraction, output columns) by operand size;
#: measured on the v5e at the OLMoE cell's shapes (PERF.md section 6, PR
#: 25): bf16 (512, 1024, 1024) runs the three gemms of a layer, forward and
#: backward, in 0.69 of ragged_dot's time; 1024 rows or 2048 columns a tile
#: run out of VMEM, and float32 tiles take twice the bytes
_GMM_TILING = {2: (512, 1024, 1024), 4: (256, 512, 512)}


def _operand_tile(tile, size):
    """The tile for an operand ``size`` wide under the table's ``tile``: the
    operand itself where it is no wider (width 768 under 1,024), the tile
    where it divides the operand, else the largest multiple of the 128
    lanes under the tile that does (1,792 = 7 x 256 takes 896: two full
    tiles where 1,024 would leave the second a quarter empty on ``n`` and a
    masked remainder on ``k``; as many grid steps, an eighth less tile
    work). An operand that no multiple of 128 divides keeps the table's
    tile, and the kernel masks its last one."""
    if size <= tile:
        return size
    return next((t for t in range(tile, 0, -128) if size % t == 0), tile)


def _gmm_tiling(rows, itemsize, k, n):
    """The kernel's tiling for this many assigned rows of a [k -> n] gemm,
    or None where it cannot take them: its row tile has to divide the
    rows. Where the table's tile does not divide an operand — it is wider
    than the operand, or the operand is no multiple of it — the answer is
    the kernel's table form, a function of each call's (m, k, n), so that
    the backward calls, whose k and n swap, are answered too: every
    operand takes ``_operand_tile``'s divisor, and no tile is masked or
    part empty."""
    want = _GMM_TILING.get(itemsize)
    if want is None:
        return None
    tm = next((t for t in (want[0], 256, 128, 64, 32, 16, 8)
               if t <= want[0] and rows % t == 0), None)
    if tm is None:
        return None
    if k % want[1] == 0 and n % want[2] == 0:
        return (tm,) + want[1:]
    return lambda m, k, n: (tm, _operand_tile(want[1], k),
                            _operand_tile(want[2], n))


def _grouped_matmul(rows, w, group_sizes, kernel):
    """rows [M, K] sorted by group, w [G, K, N] -> [M, N]: each row times
    its own group's matrix; exact under any group sizes. ``kernel`` as
    ``ops.placement.kernel(sharded=False)`` says where the op is dispatched
    (no shard_map here: None under a mesh of several devices, as on a CPU:
    ``jax.lax.ragged_dot``, which XLA partitions); the megablox kernel's
    row tile has to divide M, else ragged_dot serves. Measured on the chip
    (PERF.md section 6, PR 25): the kernel is the faster, and XLA's TPU
    expansion of ragged_dot loses the operation's scope in a trace."""
    tiling = _gmm_tiling(rows.shape[0], rows.dtype.itemsize, *w.shape[1:])
    if kernel is not None and tiling is not None and rows.dtype == w.dtype:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, w, group_sizes, rows.dtype, tiling,
                   interpret=kernel == "interpret")
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=rows.dtype)


def _expert_gemms(xs, w_gate, w_up, w_down, group_sizes, kernel,
                  activation="gelu"):
    """The experts on rows in expert order: two grouped matmuls with gelu
    or relu² (in float32) between, or three with SwiGLU (its product in
    float32)."""
    with jax.named_scope("moe.experts"):
        def grouped(rows, w):
            return _grouped_matmul(rows, w, group_sizes, kernel)

        up = grouped(xs, w_up)
        if w_gate is not None:
            mid = (jax.nn.silu(grouped(xs, w_gate).astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(xs.dtype)
        elif activation == "gelu":
            mid = jax.nn.gelu(up)
        else:
            mid = _relu2(up.astype(jnp.float32)).astype(xs.dtype)
        return grouped(mid, w_down)


def _sorted_experts(x, topi, w_gate, w_up, w_down, *, kernel=None,
                    activation="gelu"):
    """Dispatch and experts of the dropless path: [B, S, H] and the expert
    ids [N, k] -> every (token, choice) pair's expert output [N*k, H] in
    expert order, with the permutation and its inverse."""
    n, k = topi.shape
    e = w_up.shape[0]
    with jax.named_scope("moe.dispatch"):
        flat = topi.reshape(-1)
        order = jnp.argsort(flat).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
        group_sizes = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32),
                              axis=0)
        xs = _rows_to_expert_order(x.reshape(n, x.shape[-1]), order, inv, k)
    ys = _expert_gemms(xs, w_gate, w_up, w_down, group_sizes, kernel,
                       activation)
    return ys, order, inv


def _combine(ys, topv, order, inv, *, shape, held=False):
    """Weight each pair's output, un-sort, sum a token's k choices (f32).
    ``held``: ys are a held share's rows (``order`` the pair computed in
    each, ``inv`` each pair's row, ``rows`` where it has none)."""
    with jax.named_scope("moe.combine"):
        n, k = topv.shape
        if held:
            out = _held_weighted_sum(ys, topv, order, inv)
        else:
            by_token = _rows_to_token_order(ys, order, inv).reshape(n, k, -1)
            out = jnp.einsum("nkh,nk->nh", by_token.astype(jnp.float32),
                             topv.astype(jnp.float32))
        return out.astype(jnp.promote_types(ys.dtype, topv.dtype)
                          ).reshape(shape)


# ------------------------------------------------------------ held share
# One chip's share of the experts (expert parallelism without the
# exchange): the router scores and picks over ALL experts, and only the
# (token, choice) pairs whose expert lives here are placed, gathered and
# multiplied. How many pairs land here depends on the data, so they go
# into a buffer of ``rows`` rows, a stated factor over the mean. The row
# bound, in the reference's words (``benchmark/references/*.py experts()``):
# held pairs in (expert, token, choice) order; a pair whose rank reaches
# ``rows`` is dropped — and COUNTED (``held_overflow``), never silently.
# Everything as wide as the hidden size is done on the ``rows`` computed
# rows: the path gathers ``rows`` rows into expert order, and sums rows
# into tokens (``_rows_to_tokens``) by gathering ``rows`` + N — no array
# of N*k rows exists, forward or backward, where 1/8 to 1/32 of the pairs
# are held. The index work and the shifted sum are jitted on their own:
# a step traces each three times a layer (forward, recomputed forward,
# backward), a start traces several steps, and an inner jit's trace is
# made once a shape.

@functools.partial(jax.jit, static_argnums=(2,))
def _token_major(taken, inv, k):
    """The computed pairs in (token, choice) order, from ``taken`` [rows]
    and ``inv`` [N*k] as ``_held_experts`` gives them: ``row_at`` [rows]
    (the row of the c-th such pair; a token's pairs are adjacent, at most
    k of them), ``token_at`` [rows] (its token, N past the last pair),
    ``first`` [N] (where a token's pairs start) and ``pairs`` [N] (how
    many it has here). Index work on N*k integers and a sort of ``rows``
    of them; nothing is as wide as the hidden size."""
    rows = taken.shape[0]
    n = inv.shape[0] // k
    kept = (inv < rows).astype(jnp.int32).reshape(n, k)
    pairs = jnp.sum(kept, axis=1)
    first = jnp.cumsum(pairs) - pairs
    place = first[:, None] + jnp.cumsum(kept, axis=1) - kept   # [N, k]
    row = jnp.arange(rows, dtype=jnp.int32)
    filled = row < jnp.sum(pairs)
    # the filled rows are a prefix in both orders, so the rest keep their
    # place and the whole is a permutation of the rows
    place = jnp.where(filled, place.reshape(-1)[taken], row)
    # its inverse by a sort: 0.02 ms for 32,768 places on the v5e where a
    # scatter of them takes 0.62 (PERF.md section 6, PR 41)
    row_at = jnp.argsort(place).astype(jnp.int32)
    token_at = jnp.where(filled, (taken // k)[row_at], n)
    return row_at, token_at, first, pairs


@functools.partial(jax.jit, static_argnums=(4,))
def _same_token_sums(vals, weights, row_at, token_at, k):
    """[rows, H] float32 whose row c is the sum of ``vals[row_at[c + d]]``
    (times ``weights[row_at[c + d]]``) over the d < k with ``token_at[c +
    d] == token_at[c]``: k static shifts of the rows in token order, the
    products and the sum in float32."""
    rows = row_at.shape[0]
    # k - 1 rows past the end, of no token, for the shifted views to read
    tail = jnp.zeros((k - 1,), jnp.int32)
    row_at = jnp.concatenate([row_at, tail])
    token_at = jnp.concatenate([token_at, tail - 1])
    ordered = vals[row_at]
    if weights is not None:
        weights = weights.astype(jnp.float32)[row_at]
    total = 0.0
    for shift in range(k):
        # the row itself, then the rows after it while the token is the same
        view = ordered[shift:shift + rows].astype(jnp.float32)
        if weights is not None:
            view = view * weights[shift:shift + rows, None]
        same = token_at[shift:shift + rows] == token_at[:rows]
        total = total + jnp.where(same[:, None], view, 0.0)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_to_tokens(vals, weights, taken, inv, k):
    """vals [rows, H] (row i belongs to pair ``taken[i]``) and each row's
    weight [rows] (None: 1) -> [N, H] in float32: every token's sum over
    its pairs computed here, zero for a token with none. Gathers only: the
    rows are put in (token, choice) order, each is summed in float32 with
    the up to k - 1 rows after it that belong to the same token
    (``_same_token_sums``), and N rows are gathered, a token's first.
    ``rows`` + N gathered rows where the pairs in token-major order would
    be N*k, most of them the zero row. The gradient is the row gather
    ``g[taken // k]`` (times the weight; a weight's is its row's dot with
    it), so a backward pass holds [rows, H] too."""
    rows = taken.shape[0]
    row_at, token_at, first, pairs = _token_major(taken, inv, k)
    total = _same_token_sums(vals, weights, row_at, token_at, k)
    return jnp.where((pairs > 0)[:, None],
                     total[jnp.minimum(first, rows - 1)], 0.0)


def _rows_to_tokens_fwd(vals, weights, taken, inv, k):
    return (_rows_to_tokens(vals, weights, taken, inv, k),
            (vals, weights, taken, inv))


def _rows_to_tokens_bwd(k, res, g):
    vals, weights, taken, inv = res
    rows = taken.shape[0]
    filled = jnp.arange(rows) < jnp.sum(inv < rows)
    g_rows = jnp.where(filled[:, None], g[taken // k].astype(jnp.float32),
                       0.0)
    if weights is None:
        return g_rows.astype(vals.dtype), None, None, None
    d_vals = weights.astype(jnp.float32)[:, None] * g_rows
    d_weights = jnp.sum(vals.astype(jnp.float32) * g_rows, axis=-1)
    return (d_vals.astype(vals.dtype), d_weights.astype(weights.dtype),
            None, None)


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_held_order(x, taken, inv, k):
    """x [N, H] -> [rows, H]: row i is the token of the i-th pair here."""
    return x[taken // k]


def _rows_to_held_order_fwd(x, taken, inv, k):
    return x[taken // k], (taken, inv)


def _rows_to_held_order_bwd(k, res, g):
    taken, inv = res
    return (_rows_to_tokens(g, None, taken, inv, k).astype(g.dtype),
            None, None)


_rows_to_held_order.defvjp(_rows_to_held_order_fwd, _rows_to_held_order_bwd)


def _held_weighted_sum(ys, topv, taken, inv):
    """ys [rows, H] and the weights [N, k] -> each token's weighted sum over
    its choices computed here [N, H] in float32: each row times its pair's
    weight, summed into its token (``_rows_to_tokens``)."""
    return _rows_to_tokens(ys, topv.reshape(-1)[taken], taken, inv,
                           topv.shape[1])


def held_rows(tokens, top_k, count, num_experts, factor, tile=512):
    """The held path's row buffer: ``factor`` times the mean number of
    pairs that land on ``count`` of ``num_experts`` experts, rounded up to
    the grouped matmul's row tile and never more than every pair."""
    mean = tokens * top_k * count / num_experts
    rows = -(-int(np.ceil(factor * mean)) // tile) * tile
    return min(rows, tokens * top_k)


@functools.partial(jax.jit, static_argnames=("first", "count", "rows"))
def _held_places(topi, *, first, count, rows):
    """Where each pair goes: the expert ids [N, k] over ALL experts ->
    ``taken`` [rows], ``inv`` [N*k], the grouped matmul's ``group_sizes``
    [count] and the overflow, as ``_held_experts`` returns them. A held
    pair's place in (expert, token, choice) order is where its expert's
    rows start plus the pairs of that expert before it: prefix sums over
    the [N, count] counts give ``inv`` with no scatter, and a sort of
    ``inv`` gives ``taken``."""
    n, k = topi.shape
    here = jax.nn.one_hot(topi - first, count, dtype=jnp.int32)  # [N, k, c]
    by_token = jnp.sum(here, axis=1)
    sizes = jnp.sum(by_token, axis=0)
    overflow = jnp.maximum(jnp.sum(sizes) - rows, 0)
    # every row belongs to a group: the last one takes the rows that no
    # held pair fills (their output meets no weight: ``inv`` never points
    # at them)
    ends = jnp.minimum(jnp.cumsum(sizes), rows).at[-1].set(rows)
    group_sizes = jnp.diff(ends, prepend=0)
    before = (jnp.cumsum(sizes) - sizes
              + jnp.cumsum(by_token, axis=0) - by_token)         # [N, c]
    place = []
    for choice in range(k):   # a token's earlier choices of the same expert
        place.append(jnp.sum(here[:, choice] * before, axis=-1))
        before = before + here[:, choice]
    place = jnp.stack(place, axis=1)
    held = jnp.sum(here, axis=-1) > 0
    inv = jnp.where(held & (place < rows), place, rows).reshape(-1)
    taken = jnp.argsort(inv)[:rows].astype(jnp.int32)
    return taken, inv, group_sizes, overflow


def _held_experts(x, topi, w_gate, w_up, w_down, *, first, rows,
                  kernel=None, activation="gelu"):
    """Dispatch and experts of a held share: [B, S, H] and the expert ids
    [N, k] over ALL experts -> the outputs [rows, H] of the pairs whose
    expert is one of the ``w_up.shape[0]`` held from ``first`` on, in
    (expert, token, choice) order; ``taken`` [rows] (the pair computed in
    each row; a pair that is not computed here in a row that no held pair
    fills), ``inv`` [N*k] (each pair's row, ``rows`` where it was not
    computed here) and the number of held pairs that did not fit (exact
    whenever it is 0)."""
    n, k = topi.shape
    with jax.named_scope("moe.dispatch"):
        taken, inv, group_sizes, overflow = _held_places(
            topi, first=first, count=w_up.shape[0], rows=rows)
        xs = _rows_to_held_order(x.reshape(n, x.shape[-1]), taken, inv, k)
    ys = _expert_gemms(xs, w_gate, w_up, w_down, group_sizes, kernel,
                       activation)
    return ys, taken, inv, overflow


def _capacity_combine(xf, probs, top_k, cap, renorm=True):
    """GShard combine/dispatch build for one token group (fig. 6 of the
    paper): tokens claim per-expert capacity slots in order, overflow
    drops. Returns (combine [N,E,C] f32, dispatch [N,E,C], top1 idx)."""
    n, e = probs.shape
    topv, topi = jax.lax.top_k(probs, top_k)           # [N, k]
    gates = topv
    if renorm:
        gates = topv / jnp.maximum(
            jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    combine = jnp.zeros((n, e, cap), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)              # slots claimed
    for j in range(top_k):
        mask_j = jax.nn.one_hot(topi[:, j], e)         # [N, E]
        # 0-indexed slot: exclusive cumsum over tokens + slots taken by
        # earlier choices (choice 0 claims before choice 1, like GShard)
        pos_in_e = jnp.cumsum(mask_j, axis=0) - mask_j + counts
        counts = counts + jnp.sum(mask_j, axis=0)
        slot = jnp.sum(pos_in_e * mask_j, axis=-1)     # [N]
        keep = (slot < cap).astype(jnp.float32)
        combine = combine + (
            gates[:, j, None, None] * keep[:, None, None]
            * mask_j[:, :, None]
            * jax.nn.one_hot(slot, cap)[:, None, :])
    dispatch = (combine > 0).astype(xf.dtype)
    return combine, dispatch, topi


def _gshard_aux(probs, topi):
    """GShard aux loss from the full softmax + top-1 routing fraction."""
    e = probs.shape[-1]
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], e), axis=0)
    imp = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac * imp)


class MoELayer(nn.Layer):
    """Top-k gated expert FFN block (pre-norm residual not included).

    forward: [B, S, H] -> [B, S, H]. The router is a softmax over all
    experts; the k selected weights are renormalised to sum to one
    (Switch/GShard style) unless ``norm_topk_prob=False`` keeps the
    softmax's own values (OLMoE). ``activation``: 'gelu' or 'relu2' (two
    matrices) or 'swiglu' (three). ``scoring='sigmoid'``, ``select_bias``,
    ``bias_update_speed``, ``routed_scale`` and ``shared_width`` give the
    DeepSeek-V3 family's layer (``renorm_eps``: what its renormalisation
    adds to the sum), ``latent_size`` Nemotron 3's LatentMoE (the experts
    ``latent_size`` wide in and out, between two shared projections),
    ``held=(first, count)`` one chip's range of
    its experts (module docstring). The auxiliary losses (load balancing times
    ``aux_weight``, router z-loss times ``z_loss_weight``) are routed
    through ``nn.aux_loss.emit_aux_loss``: in eager mode they land on
    ``self.aux_loss`` (add it to the objective yourself); inside
    ``spmd.build_train_step`` / ``comm_opt`` train steps they are collected
    into the compiled loss automatically; in inference traces
    (jit.save / onnx.export / generation) they are dropped so no tracer
    escapes onto the layer. Pipeline/FSDP per-stage applies currently
    drop them too — add the aux term explicitly there if it matters.
    """

    def __init__(self, hidden_size, ffn_hidden, num_experts, top_k=2,
                 shard_axis="ep", aux_weight=0.01, dispatch_mode="auto",
                 capacity_factor=1.25, activation="gelu", gate_bias=True,
                 norm_topk_prob=True, z_loss_weight=0.0, weight_attr=None,
                 scoring="softmax", select_bias=False, bias_update_speed=0.0,
                 routed_scale=1.0, shared_width=0, held=None,
                 held_rows_factor=2.0, shared_gate=False, renorm_eps=1e-20,
                 latent_size=0):
        super().__init__()
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.aux_weight = float(aux_weight)
        self.z_loss_weight = float(z_loss_weight)
        self.norm_topk_prob = bool(norm_topk_prob)
        if dispatch_mode not in ("auto", "dense", "capacity", "alltoall"):
            raise ValueError(f"dispatch_mode must be 'auto'/'dense'/"
                             f"'capacity'/'alltoall', got {dispatch_mode!r}")
        if activation not in ("gelu", "relu2", "swiglu"):
            raise ValueError(f"activation must be 'gelu', 'relu2' or "
                             f"'swiglu', got {activation!r}")
        self.activation = activation
        self.latent_size = int(latent_size)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                             f"{scoring!r}")
        self.scoring = scoring
        self.routed_scale = float(routed_scale)
        self.renorm_eps = float(renorm_eps)
        self.bias_update_speed = float(bias_update_speed)
        first, count = (0, self.num_experts) if held is None else held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"held=(first, count)={held!r} is no range of "
                             f"the {self.num_experts} experts")
        self.held = None if held is None else (int(first), int(count))
        self.held_rows_factor = float(held_rows_factor)
        if self.held and dispatch_mode != "auto":
            raise ValueError("a held share of the experts runs the sorted "
                             "path: leave dispatch_mode='auto'")
        self.shard_axis = shard_axis
        self.dispatch_mode = dispatch_mode
        self.capacity_factor = float(capacity_factor)
        self.gate = nn.Linear(hidden_size, num_experts,
                              weight_attr=weight_attr,
                              bias_attr=None if gate_bias else False)
        # the experts' width in and out: the latent space's, else the
        # router's own
        width = self.latent_size or hidden_size
        k = 1.0 / np.sqrt(width)
        k2 = 1.0 / np.sqrt(ffn_hidden)

        def stacked(shape, bound):
            w = self.create_parameter(
                shape, attr=weight_attr,
                default_initializer=nn.initializer.Uniform(-bound, bound))
            # experts live sharded over 'ep' (spmd.build_train_step honors
            # mp_spec); the contraction over the expert dim emits the psum.
            # A held share IS one device's shard: nothing left to divide
            if self.held is None:
                w.mp_spec = P(shard_axis)
            return w

        self.w_gate = (stacked([count, width, ffn_hidden], k)
                       if activation == "swiglu" else None)
        self.w_up = stacked([count, width, ffn_hidden], k)
        self.w_down = stacked([count, ffn_hidden, width], k2)
        # LatentMoE's two projections, shared by all experts (and whole on
        # every chip of a held deployment)
        self.latent_down = self.latent_up = None
        if self.latent_size:
            self.latent_down = nn.Linear(hidden_size, width,
                                         weight_attr=weight_attr,
                                         bias_attr=False)
            self.latent_up = nn.Linear(width, hidden_size,
                                       weight_attr=weight_attr,
                                       bias_attr=False)
        self.shared = None
        if shared_width:
            # the expert every token takes (DeepSeek's shared expert), on
            # the hidden-wide stream: relu² beside relu² experts
            from ..text.models import LlamaMLP, Relu2MLP

            mlp = Relu2MLP if activation == "relu2" else LlamaMLP
            self.shared = mlp(hidden_size, shared_width, weight_attr)
        # Qwen3-Next's: the shared expert behind a gate of its own, a token
        self.shared_gate = (nn.Linear(hidden_size, 1, weight_attr=weight_attr,
                                      bias_attr=False)
                            if shared_gate and shared_width else None)
        if select_bias:
            # DeepSeek-V3's e_score_correction_bias: no gradient, moved
            # after each training step against the step's loads
            self.register_buffer("e_score_correction_bias", Tensor(
                np.zeros(self.num_experts, np.float32)))
        else:
            self.e_score_correction_bias = None
        if self.held:
            # held pairs that did not fit the row buffer, summed over the
            # training steps so far: 0 means every step was exact
            self.register_buffer("held_overflow",
                                 Tensor(np.zeros((), np.int32)))
        self.aux_loss = None

    def resolved_mode(self):
        """The path a call takes now: the mode asked for, or for ``auto``
        what the layer can observe — below 8 experts ``dense``; from 8 on
        ``capacity`` where the program's mesh (the one a step builder
        announced, else the global one) shards the experts over an axis
        larger than 1, and the dropless ``sorted`` path where none does."""
        if self.dispatch_mode != "auto":
            return self.dispatch_mode
        if self.held:
            return "sorted_held"
        if self.num_experts < _DENSE_BELOW:
            return "dense"
        sharded = placement.axis_size(self.shard_axis, or_global=True) > 1
        return "capacity" if sharded else "sorted"

    def forward(self, x):
        from ..nn.aux_loss import emit_aux_loss

        mode = self.resolved_mode()
        _DISPATCH_TOTAL.inc(path=mode)
        _LAYER_TOTAL.inc(activation=self.activation,
                         latent=str(self.latent_size))
        if mode in ("sorted", "sorted_held"):
            # what the experts read: the stream itself, or its latent form
            inner = x
            if self.latent_size:
                with jax.named_scope("latentmoe.down"):
                    inner = self.latent_down(x)
            out, aux = self._forward_sorted(x, inner)
            emit_aux_loss(self, aux)
            if self.latent_size:
                with jax.named_scope("latentmoe.up"):
                    out = self.latent_up(out)
            if self.shared is None:
                return out
            with jax.named_scope("moe.shared"):
                shared = self.shared(x)
                if self.shared_gate is not None:
                    shared = nn.functional.sigmoid(self.shared_gate(x)) * shared
                return out + shared
        if (self.scoring != "softmax" or self.shared is not None
                or self.activation == "relu2" or self.latent_size):
            raise NotImplementedError(
                f"the {mode} path has the softmax router, gelu or SwiGLU "
                "experts on the hidden-wide stream and no shared expert; "
                "sigmoid scoring, a shared expert, relu² and a latent space "
                "run on the sorted paths")
        logits = self.gate(x)  # [B, S, E]

        def _moe(x, logits, w_gate, w_up, w_down, *, top_k, renorm):
            e = logits.shape[-1]
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            # exact top-k mask from indices (a >=threshold compare would
            # select every tied expert, e.g. all of them on the uniform
            # probs a zero/padding token produces)
            idx = jax.lax.top_k(probs, top_k)[1]            # [B, S, k]
            mask = jnp.sum(jax.nn.one_hot(idx, e, dtype=probs.dtype),
                           axis=-2)
            mask = jnp.minimum(mask, 1.0)
            gates = probs * mask
            if renorm:
                gates = gates / jnp.maximum(
                    jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
            # dense dispatch: every expert on every token, gated sum.
            # w_up/w_down sharded on e -> per-shard partial experts; the
            # final contraction over e all-reduces over 'ep'.
            y = _expert_ffn(x, w_gate, w_up, w_down,
                            "bsh,ehf->besf", "besf,efh->besh")
            out = jnp.einsum("bse,besh->bsh", gates.astype(y.dtype), y)
            # GShard aux loss: E * sum_e (frac tokens routed to e *
            # mean gate prob of e)
            frac = jnp.mean(mask, axis=(0, 1))
            imp = jnp.mean(probs, axis=(0, 1))
            aux = e * jnp.sum(frac / top_k * imp)
            return out, aux.astype(x.dtype)

        def _moe_capacity(x, logits, w_gate, w_up, w_down, *, top_k,
                          cap_factor, renorm):
            """GShard top-k capacity dispatch (Lepikhin et al. 2020,
            algorithm in fig. 6): one-hot dispatch/combine einsums with
            per-expert capacity C and drop-overflow. Static shapes; the
            ep-sharded [E, C, H] buffers make the dispatch einsum the
            cross-expert shuffle (XLA picks the collective)."""
            b, s, hdim = x.shape
            e = logits.shape[-1]
            n = b * s
            cap = max(1, int(np.ceil(cap_factor * top_k * n / e)))
            xf = x.reshape(n, hdim)
            probs = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1).reshape(n, e)
            combine, dispatch, topi = _capacity_combine(xf, probs, top_k,
                                                        cap, renorm)
            buf = jnp.einsum("nec,nh->ech", dispatch, xf)
            y = _expert_ffn(buf, w_gate, w_up, w_down,
                            "ech,ehf->ecf", "ecf,efh->ech")
            out = jnp.einsum("nec,ech->nh", combine.astype(y.dtype), y)
            aux = _gshard_aux(probs, topi)
            return out.reshape(b, s, hdim), aux.astype(x.dtype)

        if mode == "alltoall":
            out, aux = self._forward_alltoall(x, logits)
        elif mode == "capacity":
            out, aux = apply_op("moe_ffn_capacity", _moe_capacity, x,
                                logits, self.w_gate, self.w_up, self.w_down,
                                top_k=self.top_k,
                                cap_factor=self.capacity_factor,
                                renorm=self.norm_topk_prob)
        else:
            out, aux = apply_op("moe_ffn", _moe, x, logits, self.w_gate,
                                self.w_up, self.w_down, top_k=self.top_k,
                                renorm=self.norm_topk_prob)
        aux = aux * self.aux_weight
        if self.z_loss_weight:
            aux = aux + self.z_loss_weight * apply_op(
                "moe_z_loss", _z_loss, logits)
        emit_aux_loss(self, aux)
        return out

    def _forward_sorted(self, x, inner):
        """The dropless path (module docstring), as three ops so that amp
        O1 casts only the expert matmuls' operands: the router and the
        weighted sum over a token's k choices stay in float32. The router
        reads ``x``, the experts ``inner`` (x itself, or its latent form)
        and the sum is as wide as ``inner``."""
        # the noaux_tc balancing moves the selection bias against this
        # step's loads: the router's own count of them leaves with its
        # choices
        balancing = bool(self.training and self.bias_update_speed
                         and self.e_score_correction_bias is not None)
        tokens = int(np.prod(x.shape[:-1]))
        topv, topi, balance, z, *n_e = apply_op(
            "moe_route", _route, x, self.gate.weight, self.gate.bias,
            self.e_score_correction_bias, top_k=self.top_k,
            renorm=self.norm_topk_prob, scoring=self.scoring,
            routed_scale=self.routed_scale, renorm_eps=self.renorm_eps,
            choice=route_kernel(tokens, self.num_experts, self.top_k),
            counts=balancing)
        kernel = placement.kernel(sharded=False)   # no shard_map of its own
        if self.held is None:
            ys, order, inv = apply_op(
                "moe_experts_sorted", _sorted_experts, inner, topi,
                self.w_gate, self.w_up, self.w_down, kernel=kernel,
                activation=self.activation)
            out = apply_op("moe_combine", _combine, ys, topv, order, inv,
                           shape=tuple(inner.shape))
        else:
            first, count = self.held
            ys, taken, inv, overflow = apply_op(
                "moe_experts_held", _held_experts, inner, topi, self.w_gate,
                self.w_up, self.w_down, first=first, rows=held_rows(
                    tokens, self.top_k, count, self.num_experts,
                    self.held_rows_factor), kernel=kernel,
                activation=self.activation)
            out = apply_op("moe_combine", _combine, ys, topv, taken, inv,
                           shape=tuple(inner.shape), held=True)
            if self.training:
                self.held_overflow.set_value(
                    self.held_overflow._value + overflow._value)
        if balancing:
            # an overloaded expert's bias falls, an underloaded one's rises,
            # by a fixed step; it is part of the train step (the buffer
            # threads through it)
            loads = n_e[0]._value
            bias = self.e_score_correction_bias
            bias.set_value(bias._value + self.bias_update_speed
                           * jnp.sign(jnp.mean(loads) - loads))
        aux = balance * self.aux_weight
        if self.z_loss_weight:
            aux = aux + z * self.z_loss_weight
        return out, aux

    def _forward_alltoall(self, x, logits):
        """Explicit GShard a2a dispatch under shard_map over 'ep' (see
        module docstring): tokens batch-sharded, experts local, two
        lax.all_to_all around the expert FFN."""
        from ..distributed import topology

        mesh = topology.get_global_mesh()
        axis = self.shard_axis
        ep = mesh.shape.get(axis, 1)
        e, top_k, cf = self.num_experts, self.top_k, self.capacity_factor
        if ep <= 1:
            raise ValueError(
                "dispatch_mode='alltoall' needs a global mesh with "
                f"{axis!r} > 1 (set_global_mesh(build_mesh(ep=...)))")
        if e % ep:
            raise ValueError(f"num_experts={e} must divide over "
                             f"{axis}={ep} for all_to_all dispatch")
        # tokens stay sharded over the data axes TOO (GShard groups =
        # product of data axes x ep; the a2a rides only the ep sub-axis)
        # — no per-step data->ep resharding. Shares shard_batch's axis
        # derivation so the incoming batch layout always matches.
        from ..distributed.topology import data_axes as _data_axes

        tok_axes = tuple(ax for ax in _data_axes(mesh)
                         if ax != axis) + (axis,)
        groups = int(np.prod([mesh.shape[ax] for ax in tok_axes]))
        b = int(x.shape[0])
        if b % groups:
            raise ValueError(f"batch {b} must be divisible by the token "
                             f"shard count {groups} (axes {tok_axes})")

        renorm = self.norm_topk_prob

        def local_fn(x, logits, *weights):
            # x: [B/groups, S, H] (groups = data axes x ep shards);
            # weights: ([w_gate,] w_up, w_down), each [E/ep, ...] (local
            # experts)
            w_gate = weights[0] if len(weights) == 3 else None
            w_up, w_down = weights[-2:]
            b_loc, s, hdim = x.shape
            n = b_loc * s
            cap = max(1, int(np.ceil(cf * top_k * n / e)))
            xf = x.reshape(n, hdim)
            probs = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1).reshape(n, e)
            combine, dispatch, topi = _capacity_combine(xf, probs, top_k,
                                                        cap, renorm)
            buf = jnp.einsum("nec,nh->ech", dispatch, xf)  # [E, C, H]
            # shard r keeps experts [r*E/ep, (r+1)*E/ep): swap the
            # expert dim across shards, stacking every shard's tokens
            # for my experts along capacity
            buf = jax.lax.all_to_all(buf, axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            y = _expert_ffn(buf, w_gate, w_up, w_down,
                            "ech,ehf->ecf", "ecf,efh->ech")
            y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                                   tiled=True)              # [E, C, H]
            out = jnp.einsum("nec,ech->nh", combine.astype(y.dtype), y)
            aux = jax.lax.pmean(_gshard_aux(probs, topi), tok_axes)
            return out.reshape(b_loc, s, hdim), aux.astype(x.dtype)

        def _a2a(x, logits, *weights):
            tok = P(tok_axes, None, None)
            wsp = P(axis, None, None)
            fn = shard_map(local_fn, mesh=mesh,
                           in_specs=(tok, tok) + (wsp,) * len(weights),
                           out_specs=(tok, P()),
                           check_vma=False)
            return fn(x, logits, *weights)

        weights = tuple(w for w in (self.w_gate, self.w_up, self.w_down)
                        if w is not None)

        from ..core.dispatch import in_trace

        if not in_trace():
            # eager values sit committed on one device; move them onto
            # the mesh IN PLACE (value-preserving, keeps tape identity —
            # the eager-collective placement pattern of collective.py)
            from jax.sharding import NamedSharding

            def _place(t, spec):
                if not isinstance(t._value, jax.core.Tracer):
                    t._value = jax.device_put(t._value,
                                              NamedSharding(mesh, spec))

            _place(x, P())
            _place(logits, P())
            for w in weights:
                _place(w, P(axis))
        # cache key must discriminate everything the closure captures:
        # the mesh's token-shard group count, and the routing params
        # (top_k / capacity_factor / num_experts) — two layers differing
        # only in top_k would otherwise share the cached jit
        return apply_op(
            f"moe_ffn_a2a_{axis}{ep}_g{groups}_m{id(mesh)}"
            f"_k{top_k}_cf{cf}_e{e}_r{int(renorm)}",
            _a2a, x, logits, *weights)
