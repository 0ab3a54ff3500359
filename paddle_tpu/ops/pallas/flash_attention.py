"""Fused multi-head attention as Pallas TPU kernels: a streaming flash
(blockwise-softmax) kernel for long keys (``mha``), and a whole-sequence
kernel for sequences whose keys fit one tile (``mha_packed``, further down:
many heads a program, no recurrence, one backward kernel, the packed QKV
projection read in place). ops/attention.py picks between them by shape.

TPU-native replacement for the reference's fused attention math
(reference: paddle/fluid/operators/math/bert_encoder_functor.cu,
paddle/fluid/operators/fused/multihead_matmul_op.cu) — those are CUDA
softmax-fused matmuls; here the idiomatic TPU design is the standard
flash-attention online-softmax recurrence tiled for the MXU:

- streaming 3-d grids: the forward runs (bh, q_blocks, k_blocks) with ONE
  K/V tile fetched per grid step (Mosaic double-buffers the DMA against
  compute); the backward runs (bh, k_blocks, q_blocks) streaming Q/dO
  tiles. Accumulators (running max/sum, output/grad partials) live in
  VMEM scratch that persists across the inner grid dimension,
  lane-replicated at [block, 128] where narrow columns would waste the
  vector registers. Causal grids skip fully-masked steps and remap
  their tile index so the revisit cache elides the dead DMA.
- a sliding window (``mha(window=)``: causal, query i on keys i - window <
  j <= i) shrinks the grids instead of gating them: the inner dimension of
  every call counts the blocks of ONE outer block's band (at 1,024-wide
  blocks and a window of 2,048 three of a 16-block row's), the index maps
  add the band's first block, and the calls carry names of their own
  (``flash_band_*``). A gate alone would leave a 16,384-token row the
  causal grid's 136 live steps a head (of 256) where the band holds 47.
- the backward is ONE kernel: it recomputes each (q block, k block)
  tile's probabilities from the saved logsumexp once and takes dv, dk
  and dq from it — dk/dv into per-k-block scratch, dq into a float32
  slab that holds a whole (batch . head) row of queries in VMEM across
  the k blocks (no S*S materialisation anywhere, no full-K/V VMEM
  residency). Where that row is past the slab's VMEM budget
  (``_one_pass_backward``: tens of thousands of queries) the backward is
  the standard two calls, dq on a (bh, q_blocks, k_blocks) grid of its
  own, each recomputing the probabilities.

All matmuls (both kernels') request `preferred_element_type=float32` so
the MXU accumulates in f32 even for bf16 inputs, and both draw dropout
masks from one counter hash (``_keep_mask``). The kernels compile via
Mosaic; ``interpret=True`` runs them in the Pallas interpreter instead
(the CPU test-suite and the chip_smoke dry run ask for it explicitly —
it is never inferred from the backend).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.random import fmix32, keep_thresh_u32
from ...obs import metrics as obs_metrics
from .. import residuals

NEG_INF = -1e30


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _block(seq, want):
    """Largest block size <= want that divides seq (>=8 when possible)."""
    for b in (want, 512, 256, 128, 64, 32, 16, 8):
        if b <= want and seq % b == 0:
            return b
    return seq  # tiny/odd seq: single block


def _mask_seed(seed, b):
    """The batch-head index folded into the seed by its own hash round
    (not a flat linear index), so masks stay decorrelated even when
    bh * seq_q * seq_k exceeds 2^32."""
    bseed = seed ^ (b.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    bseed ^= bseed >> jnp.uint32(13)
    return bseed * jnp.uint32(0xC2B2AE35)


def _mask_index(rows, cols, seq_k):
    """The element's term of the hash: its (query row, key column) index,
    spread by the golden-ratio multiplier. It does not depend on the seed,
    so a kernel that hashes many heads computes it once."""
    return ((rows * _i32(seq_k) + cols).astype(jnp.uint32)
            * jnp.uint32(0x9E3779B1))


def _keep_mask(seed, b, rows, cols, seq_q, seq_k, keep_thresh):
    """Counter-based dropout mask: a murmur-style hash of the global element
    index (b, row, col), so forward and backward kernels regenerate
    bit-identical masks from the same seed with no PRNG state — pure uint32
    vector math that lowers on both Mosaic and interpret mode (the pltpu
    hardware PRNG has no interpret-mode lowering)."""
    h = fmix32(_mask_index(rows, cols, seq_k) ^ _mask_seed(seed, b))
    return h < jnp.uint32(keep_thresh)


# ---------------------------------------------------------------- forward

LANES = 128

# the forward and the two-call backward run (outer, outer, streamed) grids:
# the outer dims are independent work; only the streamed accumulation dim
# is order-dependent (the one-pass backward accumulates dq across its k
# blocks too, and says so in its own parameters)
_STREAM_GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _clip_block(raw, num_blocks):
    """A block index clamped into its grid: an int32 scalar inside a kernel
    or an index map, a Python int where ``_band_steps`` sizes a grid."""
    if isinstance(raw, int):
        return min(max(raw, 0), num_blocks - 1)
    return jnp.clip(raw, 0, num_blocks - 1).astype(jnp.int32)


def _causal_last_kb(q_block, block_q, block_k, offset, num_kb):
    """Index of the LAST k block the rows of ``q_block`` attend to under
    bottom-right-aligned causal masking (row r attends cols <= r+offset),
    clamped into the grid. Single source for the in-kernel compute gates
    AND the DMA index-map remaps — the two must stay bit-identical or a
    kernel computes against a tile the index map never fetched."""
    raw = (q_block * block_q + block_q - 1 + offset) // block_k
    return _clip_block(raw, num_kb)


def _causal_first_qb(k_block, block_q, block_k, offset, num_qb):
    """Index of the FIRST q block with any unmasked row for ``k_block``
    (mirror of _causal_last_kb for the dk/dv streaming grid)."""
    raw = (k_block * block_k - offset) // block_q
    return _clip_block(raw, num_qb)


# A sliding window of ``window`` keys (row r attends cols c with r - window
# < c <= r; seq_q == seq_k, so no offset) gives the causal edge a lower
# twin. The band's grids count the band's blocks alone: the inner dimension
# of a banded call has ``_band_steps`` steps, step j of outer block i is
# inner block ``first(i) + j``, live while it is not past ``last(i)``.
def _band_first_kb(q_block, block_q, block_k, window, num_kb):
    """Index of the FIRST k block any row of ``q_block`` attends to under
    the window (lower-edge twin of _causal_last_kb; the same single source
    for gates and index maps)."""
    raw = (q_block * block_q - (window - 1)) // block_k
    return _clip_block(raw, num_kb)


def _band_last_qb(k_block, block_q, block_k, window, num_qb):
    """Index of the LAST q block with a row that still sees ``k_block``
    under the window (upper-edge twin of _causal_first_qb)."""
    raw = (k_block * block_k + block_k - 1 + window - 1) // block_q
    return _clip_block(raw, num_qb)


def _band_steps(num_outer, first, last):
    """The most inner blocks one outer block's band holds (static)."""
    return max(last(i) - first(i) + 1 for i in range(num_outer))


def band_pairs(seq, window):
    """(query, key) pairs of one head under a causal window:
    ``sum_i min(i + 1, window)``."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _band_index_maps(block_q, block_k, window, nqb, nkb):
    """(k/v tile of (q block i, step j), q tile of (k block i, step j),
    steps of a q block's band, steps of a k block's band): a step past its
    band's last block maps to that block again, so the revisit cache
    elides its DMA (the causal kernels' dedup, at both edges)."""
    def first_kb(i):
        return _band_first_kb(i, block_q, block_k, window, nkb)

    def last_kb(i):
        return _causal_last_kb(i, block_q, block_k, 0, nkb)

    def first_qb(i):
        return _causal_first_qb(i, block_q, block_k, 0, nqb)

    def last_qb(i):
        return _band_last_qb(i, block_q, block_k, window, nqb)

    def kv_index(b, i, j):
        return (b, jnp.minimum(first_kb(i) + j, last_kb(i)), 0)

    def q_index(b, i, j):
        return (b, jnp.minimum(first_qb(i) + j, last_qb(i)), 0)

    return (kv_index, q_index, _band_steps(nqb, first_kb, last_kb),
            _band_steps(nkb, first_qb, last_qb))


def _band_cost(bh, seq, d, dv, window, itemsize, products, arrays):
    """A banded call's cost: ``products`` (pairs x width) multiply-adds a
    head over the band's pairs, ``arrays`` [seq, width] reads and writes."""
    pairs = bh * band_pairs(seq, window)
    return pl.CostEstimate(flops=2 * pairs * products,
                           bytes_accessed=bh * seq * arrays * itemsize,
                           transcendentals=pairs)


def _visible(rows, cols, offset, window):
    """The causal mask of a score tile, with the window's lower edge where
    there is one."""
    seen = rows + _i32(offset) >= cols
    if window is not None:
        seen &= cols > rows - _i32(window)
    return seen


def _lane_bcast(block_q, n):
    """Lane-group broadcast ([block_q, LANES] -> [block_q, n]): a tile is
    a cheap lane copy when n is lane-aligned; odd widths fall back to a
    column broadcast."""
    if n % LANES == 0:
        return lambda a: jnp.tile(a, (1, n // LANES))
    return lambda a: jnp.broadcast_to(a[:, :1], (block_q, n))


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, causal, block_q, block_k,
                seq_q, seq_k, offset, dropout_p, keep_thresh, window=None):
    """Streaming-grid flash forward: grid (bh, q_blocks, k_blocks) with k
    innermost, one K/V tile per grid step (Mosaic double-buffers the tile
    DMA against compute — the full-K/V-in-VMEM design it replaces was
    bound by per-program overhead and capped at seq ~16k by the 16 MB
    scoped VMEM limit). Running max/sum/acc live in VMEM scratch that
    persists across the k steps of one q block; they are LANE-REPLICATED
    at [block_q, LANES] because narrow-column f32 arrays waste the
    (8,128) vector registers and force a relayout on every online-softmax
    update. MXU inputs stay in the source dtype (bf16): casting to f32
    forces multi-pass f32 MXU matmuls, measured ~8x slower; accumulation
    is f32 via preferred_element_type, and the softmax scale is applied
    to the f32 scores rather than pre-scaling q.

    With ``window`` the grid's inner dimension counts the band's k blocks:
    step ``ji`` is k block ``_band_first_kb(qi) + ji``."""
    bi = _i32(pl.program_id(0))
    qi = _i32(pl.program_id(1))
    ji = _i32(pl.program_id(2))
    seed = seed_ref[0, 0].astype(jnp.uint32)
    num_kb = seq_k // block_k
    ki = ji if window is None else ji + _band_first_kb(
        qi, block_q, block_k, window, num_kb)
    q_start = qi * _i32(block_q)
    k_start = ki * _i32(block_k)
    bcast_k = _lane_bcast(block_q, block_k)
    bcast_d = _lane_bcast(block_q, v_ref.shape[-1])

    if causal:
        # the last k block this q block attends to; later ones are
        # skipped entirely (compute AND the finalize write both key off
        # it, so the output is stored exactly once). The clamp means a
        # fully-masked q block (seq_q > seq_k with causal) still
        # finalizes — writing the zeros the masked rows deserve —
        # instead of leaving the output block unwritten.
        last_kb = _causal_last_kb(qi, block_q, block_k, offset, num_kb)
        needed = k_start <= q_start + _i32(block_q - 1 + offset)
    else:
        last_kb = _i32(num_kb - 1)
        needed = None

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _compute():
        q = q_ref[0]                                    # [block_q, d]
        k = k_ref[0]                                    # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, block_k]
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            s = jnp.where(_visible(rows, cols, offset, window), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - bcast_k(m_new))
        if causal:
            # a row with EVERY entry masked has m_new == NEG_INF, making
            # exp(s - m) = exp(0) = 1 across the row — zero those entries
            # so fully-masked rows produce o = 0, not the mean of v
            p = jnp.where(s == NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        # dropout applies to softmax probs: l accumulates the undropped
        # sum (the normalizer), acc the dropped numerator
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        if dropout_p > 0.0:
            keep = _keep_mask(seed, bi, rows, cols, seq_q, seq_k,
                              keep_thresh)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        acc_ref[...] = acc_ref[...] * bcast_d(alpha) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ki == last_kb)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / bcast_d(l)).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l))[:, :1]  # [block_q, 1]


def _keep_thresh(dropout_p):
    return keep_thresh_u32(1.0 - dropout_p)


def _fwd(q, k, v, seed, scale, causal, block_q, block_k, dropout_p,
         interpret, window=None):
    bh, seq_q, d = q.shape
    seq_k, dv = k.shape[1], v.shape[2]
    k_steps, name = seq_k // block_k, "flash_stream_fwd"
    cost = pl.CostEstimate(
        flops=2 * seq_q * seq_k * (d + dv),
        bytes_accessed=((seq_q + seq_k) * d + seq_k * dv) * q.dtype.itemsize,
        transcendentals=seq_q * seq_k)
    out_shape = (
        jax.ShapeDtypeStruct((bh, seq_q, dv), q.dtype),
        # lse kept 3-d with trailing dim 1: TPU block shapes must tile
        # (8,128) or match the array dims exactly
        jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
    )
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=seq_q, seq_k=seq_k,
        offset=seq_k - seq_q, dropout_p=dropout_p,
        keep_thresh=_keep_thresh(dropout_p), window=window)
    if window is not None:
        # the band alone: the inner dimension counts ITS k blocks, under a
        # Mosaic name of its own (a trace tells a banded call from a full
        # one); q, k read (d), v read and o written (dv)
        kv_index, _, k_steps, _ = _band_index_maps(
            block_q, block_k, window, seq_q // block_q, k_steps)
        name = "flash_band_fwd"
        cost = _band_cost(bh, seq_q, d, dv, window, q.dtype.itemsize,
                          d + dv, 2 * d + 2 * dv)
    elif causal:
        # skipped upper-triangle k steps map to the last NEEDED tile of
        # their q block, so Mosaic's revisit cache dedups the DMA — the
        # pl.when compute gate alone would still fetch every skipped
        # K/V tile from HBM
        off = seq_k - seq_q
        nkb = seq_k // block_k

        def kv_index(b, i, j):
            last = _causal_last_kb(i, block_q, block_k, off, nkb)
            return (b, jnp.minimum(j, last), 0)
    else:
        kv_index = lambda b, i, j: (b, j, 0)  # noqa: E731
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // block_q, k_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),      # output acc
        ],
        interpret=interpret,
        name=name,
        compiler_params=_STREAM_GRID_PARAMS,
        cost_estimate=cost,
    )(seed, q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, causal, block_q, block_k,
                   seq_q, seq_k, offset, dropout_p, keep_thresh,
                   window=None):
    """Streaming dq: grid (bh, q_blocks, k_blocks), one K/V tile per step
    (same design as _fwd_kernel — no full-K/V VMEM residency, no seq
    cap); the dq accumulator lives in VMEM scratch across the k steps.
    Dot inputs stay in the source dtype; scale is applied to the f32
    scores and folded into dq at the finalize step. With ``window`` the
    inner dimension counts the band's k blocks, as the forward's."""
    bi = _i32(pl.program_id(0))
    qi = _i32(pl.program_id(1))
    ji = _i32(pl.program_id(2))
    seed = seed_ref[0, 0].astype(jnp.uint32)
    num_kb = seq_k // block_k
    ki = ji if window is None else ji + _band_first_kb(
        qi, block_q, block_k, window, num_kb)
    q_start = qi * _i32(block_q)
    k_start = ki * _i32(block_k)

    if causal:
        last_kb = _causal_last_kb(qi, block_q, block_k, offset, num_kb)
        needed = k_start <= q_start + _i32(block_q - 1 + offset)
    else:
        last_kb = _i32(num_kb - 1)
        needed = None

    @pl.when(ji == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                # [block_q, 1]
        delta = delta_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            s = jnp.where(_visible(rows, cols, offset, window), s, NEG_INF)
        p = jnp.exp(s - lse)                            # [bq, bk]
        if causal:
            # fully-masked rows have lse ~= NEG_INF, so exp(s - lse)
            # cancels to 1 on masked entries; zero them (see _fwd_kernel)
            p = jnp.where(s == NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed, bi, rows, cols, seq_q, seq_k,
                              keep_thresh)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - delta)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ki == last_kb)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *rest, scale, causal, block_q, block_k,
                    seq_q, seq_k, offset, dropout_p, keep_thresh, with_dq,
                    window=None):
    """Streaming dk/dv: grid (bh, k_blocks, q_blocks), one Q/dO tile per
    step; dk/dv accumulators in VMEM scratch. The last q block always
    attends every k block (causal or not), so the finalize write keys
    off qi == num_qb - 1 unconditionally.

    ``with_dq`` (the one-pass backward, wherever ``_one_pass_backward``
    says the slab fits): the score tile's ``ds`` gives dq too, so no second
    call recomputes q.k^T, exp and dO.v^T. ``ds . k`` is added into rows
    [q_start, q_start + block_q) of a float32 slab [seq_q, d] that stays in
    VMEM for the whole (batch . head) row of the grid: zeroed at the row's
    first k block, added to in ascending k (the order, operands and
    accumulator dtype of ``_bwd_dq_kernel``'s scratch: the same bits), and
    scaled and cast into the [seq_q, d] output block when the q block has
    met its last needed k block. The output block's index moves with the
    (batch . head) index alone, so it is written back once a row.

    With ``window`` the inner dimension counts the band's q blocks: step
    ``ji`` is q block ``_causal_first_qb(ki) + ji``, live up to
    ``_band_last_qb(ki)``, where dk and dv are written; a q block's dq rows
    are zeroed at the first k block of ITS band and written at the last."""
    if with_dq:
        dq_ref, dk_acc_ref, dv_acc_ref, dq_acc_ref = rest
    else:
        dk_acc_ref, dv_acc_ref = rest
    bi = _i32(pl.program_id(0))
    ki = _i32(pl.program_id(1))
    ji = _i32(pl.program_id(2))
    seed = seed_ref[0, 0].astype(jnp.uint32)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    if window is None:
        qi, last_qb = ji, _i32(num_qb - 1)
    else:
        qi = ji + _causal_first_qb(ki, block_q, block_k, offset, num_qb)
        last_qb = _band_last_qb(ki, block_q, block_k, window, num_qb)
    k_start = ki * _i32(block_k)
    q_start = qi * _i32(block_q)
    q_rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)

    if window is not None:
        # the band starts at the diagonal's q block: only its end gates
        needed = qi <= last_qb
    elif causal:
        # q blocks strictly before the diagonal see only masked rows
        needed = q_start + _i32(block_q - 1 + offset) >= k_start
    else:
        needed = None

    @pl.when(ji == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, jnp.float32)
        dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, jnp.float32)

    if with_dq:
        if window is None:
            first_visit = ki == 0
        else:
            first_visit = needed & (ki == _band_first_kb(
                qi, block_q, block_k, window, num_kb))

        @pl.when(first_visit)
        def _init_dq():
            dq_acc_ref[q_rows, :] = jnp.zeros(
                (block_q, dq_acc_ref.shape[-1]), jnp.float32)

    def _compute():
        k = k_ref[0]                                    # [block_k, d]
        v = v_ref[0]
        q = q_ref[0]                                    # [block_q, d]
        do = do_ref[0]
        lse = lse_ref[0]                                # [block_q, 1]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            s = jnp.where(_visible(rows, cols, offset, window), s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            # see _fwd_kernel: zero masked entries of fully-masked rows
            p = jnp.where(s == NEG_INF, 0.0, p)
        if dropout_p > 0.0:
            keep = _keep_mask(seed, bi, rows, cols, seq_q, seq_k,
                              keep_thresh)
            inv = 1.0 / (1.0 - dropout_p)
            p_d = jnp.where(keep, p * inv, 0.0)
        else:
            p_d = p
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p_d.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            dq_acc_ref[q_rows, :] = dq_acc_ref[q_rows, :] + (
                jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))

    if causal:
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(qi == last_qb)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)

    if with_dq:
        last_kb = (_causal_last_kb(qi, block_q, block_k, offset, num_kb)
                   if causal else _i32(num_kb - 1))
        last_visit = ki == last_kb
        if window is not None:
            last_visit &= needed

        @pl.when(last_visit)
        def _finalize_dq():
            dq_ref[0, q_rows, :] = (
                dq_acc_ref[q_rows, :] * scale).astype(dq_ref.dtype)


_BACKWARD_TOTAL = obs_metrics.counter(
    "paddle_tpu_flash_stream_backward_total",
    "streaming flash backward passes by their Mosaic calls (one: dq, dk "
    "and dv from one set of score tiles | two: the dq slab is over its "
    "VMEM budget); under jit one count per traced backward",
    labelnames=("calls",))

# The one-pass backward's VMEM: beside what the two-call kernel holds at
# the blocks ``_stream_block`` picks (within the 16 MiB default scoped
# limit: tests/test_mosaic_compile.py) and one more [block_q, d] f32
# product, the dq slab and its double-buffered output block may take the
# budget; a v5e core has 128 MiB.
_ONE_PASS_VMEM_LIMIT = 64 * 2**20
_ONE_PASS_SLAB_BUDGET = 40 * 2**20


def _in_lanes(d):
    """A row of width d as VMEM holds it: rounded up to the 128 lanes."""
    return -(-d // LANES) * LANES


def _one_pass_backward(seq_q, d_qk, itemsize):
    """Whether the backward takes dq from the dk/dv call's score tiles: it
    does where a whole (batch . head) row of dq — the float32 slab
    [seq_q, d_qk] and the double-buffered output block in the input dtype,
    lane-padded — fits ``_ONE_PASS_SLAB_BUDGET``. 8,192 x 192 bf16 (the
    JoyAI cell) takes 16 MiB, in float32 24; 4,096 x 128 bf16 (OLMoE) 4;
    past the budget (32,768 queries of 192 in bf16: 64 MiB) the backward is
    the two calls, each streaming its own accumulator."""
    return (seq_q * _in_lanes(d_qk) * (4 + 2 * itemsize)
            <= _ONE_PASS_SLAB_BUDGET)


def _bwd(scale, causal, block_q, block_k, dropout_p, interpret, res, do,
         window=None):
    q, k, v, o, lse, seed = res
    bh, seq_q, d = q.shape
    seq_k, dv = k.shape[1], v.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [bh, seq_q, 1]
    off = seq_k - seq_q
    nkb = seq_k // block_k
    nqb = seq_q // block_q
    one_pass = _one_pass_backward(seq_q, d, q.dtype.itemsize)
    _BACKWARD_TOTAL.inc(calls="one" if one_pass else "two")
    statics = dict(scale=scale, causal=causal, block_q=block_q,
                   block_k=block_k, seq_q=seq_q, seq_k=seq_k, offset=off,
                   dropout_p=dropout_p, keep_thresh=_keep_thresh(dropout_p))
    # the grids' inner dimensions: every block, or the band's alone
    k_steps, q_steps, name = nkb, nqb, "flash_stream_bwd_"
    dkv_dq_cost = dq_cost = dkv_cost = None

    if window is not None:
        statics["window"] = window
        kv_index, q_index, k_steps, q_steps = _band_index_maps(
            block_q, block_k, window, nqb, nkb)
        # a name of their own: a trace tells a banded call from a full one
        name = "flash_band_bwd_"
        cost = functools.partial(_band_cost, bh, seq_q, d, dv, window,
                                 q.dtype.itemsize)
        # products a pair (scores, dq, dk: d; dp, dv: dv), then arrays
        # moved (q, k, dq, dk: d; v, dO, dv: dv) of what each call makes
        dkv_dq_cost = cost(3 * d + 2 * dv, 4 * d + 3 * dv)
        dq_cost = cost(2 * d + dv, 3 * d + 2 * dv)
        dkv_cost = cost(2 * d + 2 * dv, 3 * d + 3 * dv)
    elif causal:
        # causal DMA dedup (see _fwd): skipped steps remap to a tile the
        # revisit cache already holds
        def kv_index(b, i, j):
            last = _causal_last_kb(i, block_q, block_k, off, nkb)
            return (b, jnp.minimum(j, last), 0)

        def q_index(b, i, j):
            first = _causal_first_qb(i, block_q, block_k, off, nqb)
            return (b, jnp.maximum(j, first), 0)
    else:
        kv_index = lambda b, i, j: (b, j, 0)  # noqa: E731
        q_index = lambda b, i, j: (b, j, 0)  # noqa: E731

    dkv_in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, dv), q_index),
        pl.BlockSpec((1, block_q, 1), q_index),
        pl.BlockSpec((1, block_q, 1), q_index),
    ]
    dkv_out_specs = [
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0)),
    ]
    dkv_out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                     jax.ShapeDtypeStruct(v.shape, v.dtype)]
    dkv_scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, dv), jnp.float32)]

    if one_pass:
        pairs = bh * seq_q * seq_k
        dk, dv, dq = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, with_dq=True, **statics),
            grid=(bh, nkb, q_steps),
            in_specs=dkv_in_specs,
            out_specs=dkv_out_specs + [
                pl.BlockSpec((1, seq_q, d), lambda b, i, j: (b, 0, 0))],
            out_shape=dkv_out_shape + [
                jax.ShapeDtypeStruct(q.shape, q.dtype)],
            scratch_shapes=dkv_scratch + [
                pltpu.VMEM((seq_q, d), jnp.float32)],
            interpret=interpret,
            # the readers of a trace find the backward by "bwd_dkv"
            name=name + "dkv_dq",
            # dq accumulates across the k blocks too
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_ONE_PASS_VMEM_LIMIT),
            cost_estimate=dkv_dq_cost or pl.CostEstimate(
                flops=2 * pairs * (3 * d + 2 * dv),
                # q, k, v, dO read; dq, dk, dv written
                bytes_accessed=bh * (2 * (seq_q + seq_k) * d
                                     + (seq_q + 2 * seq_k) * dv)
                * q.dtype.itemsize,
                transcendentals=pairs),
        )(seed, q, k, v, do, lse, delta)
        return dq, dk, dv

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **statics),
        grid=(bh, nqb, k_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=name + "dq",
        compiler_params=_STREAM_GRID_PARAMS,
        cost_estimate=dq_cost,
    )(seed, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, with_dq=False, **statics),
        grid=(bh, nkb, q_steps),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shape,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
        name=name + "dkv",
        compiler_params=_STREAM_GRID_PARAMS,
        cost_estimate=dkv_cost,
    )(seed, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seed, scale, causal, block_q, block_k, dropout_p,
           interpret, window):
    o, _ = _fwd(q, k, v, seed, scale, causal, block_q, block_k, dropout_p,
                interpret, window)
    return o


def _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k, dropout_p,
               interpret, window):
    o, lse = _fwd(q, k, v, seed, scale, causal, block_q, block_k, dropout_p,
                  interpret, window)
    # what only this call can make: a recomputed block keeps the two (its
    # second forward then needs no kernel call); q, k and v come back from
    # the block's projections
    o = residuals.offer(o, "flash_stream.out")
    # as [bh, seq_q]: a trailing dimension of one is padded to the 128
    # lanes in HBM, 128 times the bytes for as long as a block keeps it.
    # STOP-GAP, the two negations (they cancel bit for bit, and fold away
    # with the reshapes where nothing keeps the array): XLA names the
    # un-padding after its operand, this kernel's call, and a trace's
    # readers would count it as one. To go with a lane-dense log-sum-exp
    # from the kernel (ROADMAP Speed 1c) or with readers that match a
    # call's own name (PERF.md section 7)
    lse = -residuals.offer((-lse).reshape(lse.shape[:2]),
                           "flash_stream.lse").reshape(lse.shape)
    return o, (q, k, v, o, lse, seed)


def _flash_bwd(scale, causal, block_q, block_k, dropout_p, interpret, window,
               res, do):
    dq, dk, dv = _bwd(scale, causal, block_q, block_k, dropout_p, interpret,
                      res, do, window)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _stream_block(d_qk, d_v, itemsize, dropout=False):
    """The streaming kernels' q and k block. A program pays a fixed latency
    per grid step, so blocks as large as VMEM allows: beside the f32 score
    tiles [block, block] the backward holds six [block, width] operand
    tiles double-buffered, so the block halves as a row's bytes double. A
    row's bytes are those of the mean of the key and the value width, each
    as VMEM holds it (rounded up to the 128 lanes: latent attention's
    192-wide keys take two lane groups, its 128-wide values one). That
    account is the default 16 MiB scoped limit's; the one-pass backward
    adds a whole row of dq (the f32 slab and its output block: 16 MiB at
    8,192 x 192 bf16) and asks for ``_ONE_PASS_VMEM_LIMIT`` instead of a
    smaller block. Measured on the v5e, forward + backward: b4 h16 s4096
    d128 bf16 causal (PERF.md section 6, PR 25) 256/256 26.2 ms, 512/512
    12.7, 1024/512 11.6, 1024/1024 11.5; b1 h32 s8192 d192/128 bf16 causal
    (PR 29) 256 51.2, 512 31.8, 1024/512 29.6, 1024 28.8; with the backward
    in one pass (PR 31) 1024: 8.63 and 22.60 (two calls: 11.52 and 28.76),
    the JoyAI shape in float32 at 512 27.5 (35.5). Compiled for a described
    v5e (tests/test_mosaic_compile.py): 1024 runs out of VMEM at 512 bytes
    a row (d128 f32, d256 bf16) and at d192/128 f32 (768), 512 does not.
    With ``dropout`` the mask hash's [block, block] tiles take what one
    more lane group a row would: d192/128 bf16 ran out of VMEM at 1024 on
    the chip with a mask (chip_smoke.py, PR 29) and not without."""
    row_bytes = (_in_lanes(d_qk) + _in_lanes(d_v)) // 2 * itemsize
    if dropout:
        row_bytes += LANES
    return 1024 if row_bytes <= 384 else 512 if row_bytes <= 768 else 256


def mha(q, k, v, *, scale=None, causal=False, dropout_p=0.0, seed=None,
        block_q=None, block_k=None, interpret=False, window=None):
    """Flash attention. q,k: [batch, heads, seq, head_dim] (or 3-d
    [batch*heads, seq, head_dim]); v the same, or with a width of its own
    (latent attention: 192-wide keys, 128-wide values). Returns q's shape
    with v's width.

    dropout_p > 0 applies dropout to the attention probabilities inside the
    kernel (counter-based mask keyed by ``seed``, an int32 scalar array —
    pass a fresh seed per step; same seed -> same mask).

    ``window`` (with ``causal``, queries and keys of one length): query i
    attends the ``window`` keys up to its own position, i - window < j <=
    i. The calls are banded ones (``flash_band_*``) whose grids hold the
    band's blocks and nothing beside them; a window of the whole sequence
    or more is the causal kernel itself.

    ``interpret=True`` runs the kernels (the forward and the one-pass
    backward; past ``_one_pass_backward``'s budget the backward is two) in
    the Pallas interpreter (any backend) instead of compiling them with
    Mosaic."""
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    if window is not None:
        if not causal or sq != sk or window < 1:
            raise ValueError(
                f"a window ({window}) takes causal attention of queries on "
                f"as many keys (causal={causal}, {sq} on {sk})")
        window = None if window >= sk else int(window)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    want = _stream_block(d, dv, q.dtype.itemsize, dropout_p > 0.0)
    bq = _block(sq, block_q or want)
    bk = _block(sk, block_k or want)
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, dv)
    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    seed2d = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    o = _flash(q3, k3, v3, seed2d, float(scale), bool(causal), bq, bk,
               float(dropout_p), bool(interpret), window)
    o = o.reshape(b, h, sq, dv)
    return o[0] if squeeze else o


# ------------------------------------------- whole-sequence ("short") kernel
#
# Where every key of a row fits one tile there is nothing to stream: no
# online-softmax recurrence, no k grid dimension, and one backward kernel
# that emits dq, dk and dv together. A program takes a block of batch
# rows with ALL their heads, straight from the packed [batch, seq, 3*embed]
# output of the fused QKV projection, and writes [batch, seq, embed] for the
# output projection: the head split/merge transposes never exist, the DMAs
# are whole contiguous rows, and nothing of size [batch, heads, seq, seq]
# reaches HBM in either pass (the residuals are the packed input and the
# row log-sum-exp).
#
# Inside, heads are taken a 128-lane group at a time (two heads of 64, one
# of 128). A head narrower than the group shares the group's 128-deep MXU
# contraction: the other heads' lanes of q (and of dO) are zeroed, which
# costs the array nothing (a 64-deep contraction fills half of it anyway),
# and the output lanes of each head are picked from its own product.
# Scores are held TRANSPOSED ([keys, queries]: keys on sublanes, queries on
# lanes) so that the row statistics (max, sum, lse, delta) are lane-dense
# [1, seq] rows: they reduce over sublanes, broadcast back for free, and
# the log-sum-exp is stored compactly ([batch, groups, heads a group, seq]
# f32; a [.., seq, 1] column would be padded to 128 lanes in HBM).

# what the backward keeps live in VMEM beside its pipelined row blocks:
# about eight f32 [seq, seq] tiles (scores, probabilities, dP, dS, the mask
# hash) = 8 MiB at 512
SHORT_MAX_SEQ = 512
_SHORT_LIVE_TILES = 8
_SHORT_VMEM_LIMIT = 48 * 2**20
# double-buffered row blocks of one program may take this much of it
_SHORT_BLOCK_BUDGET = 12 * 2**20
# blocks of a batch row, in [seq, embed] arrays: the packed projection and
# the output forward; the projection, dO and the packed gradient backward
_SHORT_FWD_BLOCKS, _SHORT_BWD_BLOCKS = 4, 7

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _short_row_bytes(seq, num_heads, head_dim, itemsize, blocks):
    """One batch row of a program's blocks, the f32 log-sum-exp included."""
    return seq * (blocks * num_heads * head_dim * itemsize + num_heads * 4)


def short_supported(seq, num_heads, head_dim, dtype):
    """Shapes the whole-sequence kernel takes: a lane-dense score tile
    (seq a multiple of 128, at most SHORT_MAX_SEQ), heads that tile the
    128 lanes (head_dim divides 128, or is a multiple of it), and a
    backward that fits VMEM at one row a program — a program holds ALL
    heads of its rows, so that grows with embed x seq x itemsize."""
    group = max(head_dim, LANES)
    if not (0 < seq <= SHORT_MAX_SEQ and seq % LANES == 0
            and (LANES % head_dim == 0 or head_dim % LANES == 0)
            and (num_heads * head_dim) % group == 0):
        return False
    row = _short_row_bytes(seq, num_heads, head_dim,
                           jnp.dtype(dtype).itemsize, _SHORT_BWD_BLOCKS)
    return 2 * row + _SHORT_LIVE_TILES * 4 * seq * seq <= _SHORT_VMEM_LIMIT


def _short_rows(batch, row_bytes):
    """Batch rows per program: the largest divisor of ``batch`` whose
    double-buffered blocks fit the budget (one row always goes)."""
    cap = min(batch, max(1, _SHORT_BLOCK_BUDGET // (2 * row_bytes)))
    return max(r for r in range(1, cap + 1) if batch % r == 0)


def _short_groups(num_heads, head_dim):
    """(lane-group width, heads per group, number of groups)."""
    width = max(head_dim, LANES)
    return width, width // head_dim, num_heads * head_dim // width


def _short_each_group(rows, groups, body):
    """``body(r, j)`` for every batch row r of the block (a loop) and every
    lane group j (unrolled). One group is a chain of dependent steps
    (matmul, reduce, exp, matmul); the unrolled groups of a row are what
    the scheduler overlaps. On the v5e at BERT's shape, forward + backward
    a layer: 2.06 ms as one loop over (row, group), 1.10 ms with a row's
    six groups unrolled, 0.97 ms with four rows' — not worth four times
    the code to trace and compile at every start."""
    def one_row(r, carry):
        for j in range(groups):
            body(r, j)
        return carry

    jax.lax.fori_loop(0, rows, one_row, 0)


def _short_head_lanes(seq, width, head_dim, per_group):
    """For each head of a lane group, the [seq, width] mask of its lanes
    (None when the head is the whole group)."""
    if per_group == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (seq, width), 1)
    return [(lane >= t * head_dim) & (lane < (t + 1) * head_dim)
            for t in range(per_group)]


def _only(mine, x):
    """x with the lanes of the group's other heads zeroed."""
    return x if mine is None else jnp.where(mine, x, jnp.zeros_like(x))


def _short_mask_index(seq):
    """_mask_index of a [keys, queries] tile: sublanes count keys, lanes
    count queries."""
    return _mask_index(
        jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1),
        jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0), seq)


def _short_fwd_kernel(seed_ref, ids_ref, qkv_ref, o_ref, lse_ref, *, scale,
                      heads, head_dim, dropout_p, keep_thresh):
    rows, seq, embed = o_ref.shape
    width, per_group, groups = _short_groups(heads, head_dim)
    row0 = _i32(pl.program_id(0)) * _i32(rows)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    head_lanes = _short_head_lanes(seq, width, head_dim, per_group)
    index = _short_mask_index(seq) if dropout_p > 0.0 else None
    inv_keep = 1.0 / (1.0 - dropout_p)

    def group(r, j):
        at = j * width
        q = qkv_ref[r, :, pl.ds(at, width)]
        k = qkv_ref[r, :, pl.ds(embed + at, width)]
        v = qkv_ref[r, :, pl.ds(2 * embed + at, width)]
        out = None
        for t, mine in enumerate(head_lanes):
            s = jax.lax.dot_general(
                k, _only(mine, q), _NT,
                preferred_element_type=jnp.float32) * scale  # [keys, queries]
            m = jnp.max(s, axis=0, keepdims=True)            # [1, seq]
            e = jnp.exp(s - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            lse_ref[r, j, t:t + 1, :] = m + jnp.log(l)
            p = e * (inv_keep / l)
            if dropout_p > 0.0:
                bh = (ids_ref[row0 + r] * _i32(heads)
                      + _i32(j * per_group + t))
                keep = (fmix32(index ^ _mask_seed(seed, bh))
                        < jnp.uint32(keep_thresh))
                p = jnp.where(keep, p, 0.0)
            o_t = jax.lax.dot_general(
                p.astype(v.dtype), v, _TN,
                preferred_element_type=jnp.float32)          # [seq, width]
            out = o_t if out is None else jnp.where(mine, o_t, out)
        o_ref[r, :, pl.ds(at, width)] = out.astype(o_ref.dtype)

    _short_each_group(rows, groups, group)


def _short_bwd_kernel(seed_ref, ids_ref, qkv_ref, do_ref, lse_ref, dqkv_ref,
                      *, scale, heads, head_dim, dropout_p, keep_thresh):
    """dq, dk and dv in one pass: with every key in the tile each
    probability is recomputed once (from the saved log-sum-exp) and used
    for all three. delta = rowsum(P * dP) comes from the tile too, in f32,
    so the forward's output is not a residual."""
    rows, seq, embed = do_ref.shape
    width, per_group, groups = _short_groups(heads, head_dim)
    row0 = _i32(pl.program_id(0)) * _i32(rows)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    head_lanes = _short_head_lanes(seq, width, head_dim, per_group)
    index = _short_mask_index(seq) if dropout_p > 0.0 else None
    inv_keep = 1.0 / (1.0 - dropout_p)

    def group(r, j):
        at = j * width
        q = qkv_ref[r, :, pl.ds(at, width)]
        k = qkv_ref[r, :, pl.ds(embed + at, width)]
        v = qkv_ref[r, :, pl.ds(2 * embed + at, width)]
        do = do_ref[r, :, pl.ds(at, width)]
        dq = dk = dv = None
        for t, mine in enumerate(head_lanes):
            s = jax.lax.dot_general(
                k, _only(mine, q), _NT,
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse_ref[r, j, t:t + 1, :])       # [keys, queries]
            dp = jax.lax.dot_general(
                v, _only(mine, do), _NT, preferred_element_type=jnp.float32)
            if dropout_p > 0.0:
                bh = (ids_ref[row0 + r] * _i32(heads)
                      + _i32(j * per_group + t))
                keep = (fmix32(index ^ _mask_seed(seed, bh))
                        < jnp.uint32(keep_thresh))
                dp = jnp.where(keep, dp * inv_keep, 0.0)
                p_drop = jnp.where(keep, p * inv_keep, 0.0)
            else:
                p_drop = p
            delta = jnp.sum(p * dp, axis=0, keepdims=True)   # [1, seq]
            ds = (p * (dp - delta)).astype(q.dtype)
            dv_t = jax.lax.dot_general(
                p_drop.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)          # [keys, width]
            dk_t = jax.lax.dot_general(
                ds, q, _NN, preferred_element_type=jnp.float32)
            dq_t = jax.lax.dot_general(
                ds, k, _TN, preferred_element_type=jnp.float32)
            if dq is None:
                dq, dk, dv = dq_t, dk_t, dv_t
            else:
                dq = jnp.where(mine, dq_t, dq)
                dk = jnp.where(mine, dk_t, dk)
                dv = jnp.where(mine, dv_t, dv)
        dt = dqkv_ref.dtype
        dqkv_ref[r, :, pl.ds(at, width)] = (dq * scale).astype(dt)
        dqkv_ref[r, :, pl.ds(embed + at, width)] = (dk * scale).astype(dt)
        dqkv_ref[r, :, pl.ds(2 * embed + at, width)] = dv.astype(dt)

    _short_each_group(rows, groups, group)


_SHORT_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel",), vmem_limit_bytes=_SHORT_VMEM_LIMIT)


def _short_statics(qkv, heads, scale, dropout_p, blocks, kernel):
    """What both pallas_calls derive from the packed shape: rows a program
    (``blocks`` [seq, embed] arrays of blocks a row), the lse layout, the
    kernel with its static arguments bound."""
    batch, seq, embed3 = qkv.shape
    head_dim = embed3 // 3 // heads
    rows = _short_rows(batch, _short_row_bytes(
        seq, heads, head_dim, qkv.dtype.itemsize, blocks))
    _, per_group, groups = _short_groups(heads, head_dim)
    kernel = functools.partial(
        kernel, scale=scale, heads=heads, head_dim=head_dim,
        dropout_p=dropout_p, keep_thresh=_keep_thresh(dropout_p))
    lse_spec = pl.BlockSpec((rows, groups, per_group, seq),
                            lambda i: (i, 0, 0, 0))
    return rows, (batch, groups, per_group, seq), lse_spec, kernel


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# Both calls are jitted on their static arguments so that the layers of a
# model share ONE trace and ONE Mosaic lowering of each kernel: tracing and
# lowering a pallas_call costs ~0.25 s, on every start (it precedes the
# compile-cache lookup), and BERT-base has 24 of them.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _short_fwd(qkv, ids, seed, heads, scale, dropout_p, interpret):
    batch, seq, embed3 = qkv.shape
    embed = embed3 // 3
    rows, lse_shape, lse_spec, kernel = _short_statics(
        qkv, heads, scale, dropout_p, _SHORT_FWD_BLOCKS, _short_fwd_kernel)
    return pl.pallas_call(
        kernel,
        grid=(batch // rows,),
        in_specs=[
            _SMEM, _SMEM,
            pl.BlockSpec((rows, seq, embed3), lambda i: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((rows, seq, embed), lambda i: (i, 0, 0)),
            lse_spec,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((batch, seq, embed), qkv.dtype),
            # the row log-sum-exp of head j * per_group + t, lane-dense
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ),
        interpret=interpret,
        compiler_params=_SHORT_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * seq * seq * embed,
            bytes_accessed=batch * seq * 4 * embed * qkv.dtype.itemsize,
            transcendentals=batch * heads * seq * seq),
    )(seed, ids, qkv)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _short_bwd(qkv, ids, seed, lse, do, heads, scale, dropout_p, interpret):
    batch, seq, embed3 = qkv.shape
    embed = embed3 // 3
    rows, _, lse_spec, kernel = _short_statics(
        qkv, heads, scale, dropout_p, _SHORT_BWD_BLOCKS, _short_bwd_kernel)
    return pl.pallas_call(
        kernel,
        grid=(batch // rows,),
        in_specs=[
            _SMEM, _SMEM,
            pl.BlockSpec((rows, seq, embed3), lambda i: (i, 0, 0)),
            pl.BlockSpec((rows, seq, embed), lambda i: (i, 0, 0)),
            lse_spec,
        ],
        out_specs=pl.BlockSpec((rows, seq, embed3), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        interpret=interpret,
        compiler_params=_SHORT_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=10 * batch * seq * seq * embed,
            bytes_accessed=batch * seq * 7 * embed * qkv.dtype.itemsize,
            transcendentals=batch * heads * seq * seq),
    )(seed, ids, qkv, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _short(qkv, ids, seed, heads, scale, dropout_p, interpret):
    return _short_fwd(qkv, ids, seed, heads, scale, dropout_p, interpret)[0]


def _short_vjp_fwd(qkv, ids, seed, heads, scale, dropout_p, interpret):
    o, lse = _short_fwd(qkv, ids, seed, heads, scale, dropout_p, interpret)
    return o, (qkv, ids, seed, lse)


def _short_vjp_bwd(heads, scale, dropout_p, interpret, res, do):
    qkv = res[0]
    return (_short_bwd(*res, do.astype(qkv.dtype), heads, scale, dropout_p,
                       interpret), None, None)


_short.defvjp(_short_vjp_fwd, _short_vjp_bwd)


def mha_packed(qkv, num_heads, *, scale=None, dropout_p=0.0, seed=None,
               row_ids=None, interpret=False):
    """Whole-sequence self-attention on the packed projection output.
    qkv: [batch, seq, 3 * embed] (q | k | v along the last axis, heads
    contiguous inside each), for shapes ``short_supported`` admits; not
    causal, no mask. Returns [batch, seq, embed], heads merged.

    Dropout as in ``mha``: the mask of head h of batch row b is the hash
    ``_keep_mask`` gives for batch-head index ``row_ids[b] * num_heads +
    h``. ``row_ids`` (int32 [batch], default 0..batch-1) are the rows'
    numbers in the whole batch: a caller that holds one shard of it passes
    that shard's, and draws the mask the unsharded call would."""
    batch, seq, embed3 = qkv.shape
    embed = embed3 // 3
    head_dim = embed // num_heads
    if embed3 != 3 * num_heads * head_dim or not short_supported(
            seq, num_heads, head_dim, qkv.dtype):
        raise ValueError(
            f"mha_packed does not take qkv {qkv.shape} with {num_heads} "
            "heads (see short_supported)")
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    if row_ids is None:
        row_ids = jnp.arange(batch, dtype=jnp.int32)
    return _short(qkv, jnp.asarray(row_ids, jnp.int32),
                  jnp.asarray(seed, jnp.int32).reshape(1, 1),
                  int(num_heads), float(scale), float(dropout_p),
                  bool(interpret))
