"""Scaled-dot-product attention: one gate, three routes, and a window.

The reference has no flash attention (SURVEY §5 long-context: absent) —
its closest analog is the fused BERT encoder functor
(reference: paddle/fluid/operators/math/bert_encoder_functor.cu). Here
``attention_route`` picks, from what it can observe (shapes, mask,
causality, platform), one of:

- ``short``: the whole-sequence Pallas kernel (ops/pallas/
  flash_attention.py ``mha_packed``) on the packed [batch, seq, 3*embed]
  projection output — unmasked, non-causal self-attention whose keys fit
  one tile (BERT at 128..512). Only ``packed_self_attention`` can take it:
  it owns the layout that makes the head transposes unnecessary.
- ``stream``: the streaming flash kernel (``mha``) on [batch, heads, seq,
  head_dim] for long keys, where XLA's S^2 logits buffer explodes. Heads
  of 64 (half a lane group a row: the cell ``lfm2-8b-a1b.train-lm-s8192-b4``,
  32 query heads at 8,192 keys), 128 (OLMoE, Trinity-Mini), 256
  (Qwen3-Next) and latent attention's 192-wide keys on 128-wide values
  (JoyAI, Kimi-Linear) each run it in a cell.
- ``xla``: the jnp implementation below, which XLA fuses into a few
  kernels — every masked call, every CPU run without the interpreter flag.

A causal call may carry a static ``window``: query i attends the
``window`` keys up to its own position (i - window < j <= i; a sliding-
window layer). The window does not choose the route: ``stream`` runs the
banded kernel (``mha(window=)``: Mosaic calls ``flash_band_*`` whose grids
hold the band's blocks alone), ``xla`` builds the band inside ``_sdpa_ref``
— never as an ``attn_mask``, which would force ``xla``. A window of the
whole sequence or more is plain causal attention on both. Windowed calls
count in ``paddle_tpu_attention_window_route_total{route}`` as well.

A selected kernel that fails raises; no route falls back to another.
Whether a program may hold a kernel at all is ``ops.placement``'s answer
(its table): ``short`` asks as a call site ``on_mesh`` shards, ``stream``
as one with no XLA way out at its lengths.
Every decision counts in ``paddle_tpu_attention_route_total{route}`` (at
trace time under jit: one count per traced call site).

**The stage before the core** — a head's RMSNorm of q and of k, rotate-half
RoPE, the split into [batch, heads, seq, head_dim] — is ``qk_heads``, by the
convolution stage's rule (``ops.linear_attention.conv_streams``):
``qk_path`` gives ``kernel`` (one Mosaic call a pass on the projected
streams, the head split its BlockSpec's index map, its float32 in VMEM;
``ops/pallas/qk_heads.py``) or ``xla`` (``_qk_xla``: float32 arrays under a
``jax.checkpoint``, what every other program runs and what the kernels are
held to), counted in ``paddle_tpu_qk_heads_total{path}``.
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..core import flags, random as random_core
from ..core.dispatch import apply_op
from ..obs import metrics as obs_metrics
from . import placement

_ROUTE_TOTAL = obs_metrics.counter(
    "paddle_tpu_attention_route_total",
    "attention calls by the route the gate chose (short | stream | xla); "
    "under jit one count per traced call site",
    labelnames=("route",))

_WINDOW_ROUTE_TOTAL = obs_metrics.counter(
    "paddle_tpu_attention_window_route_total",
    "attention calls with a sliding window by the route the gate chose "
    "(stream: the banded kernel | xla: the band built in the jnp path); "
    "counted beside paddle_tpu_attention_route_total, one per traced call "
    "site",
    labelnames=("route",))

_QK_TOTAL = obs_metrics.counter(
    "paddle_tpu_qk_heads_total",
    "softmax attention's stages before the core (a head's RMSNorm of q and "
    "k, rotate-half RoPE, the head split) by the path taken: kernel (one "
    "Mosaic call a pass) | xla; one count per traced layer call",
    labelnames=("path",))

# the stream kernel runs one k block of up to 256 keys per grid step: with
# fewer keys than two blocks a program is per-program overhead however
# long q is, so the logits-product clause needs this many keys too
_STREAM_MIN_KEYS = 512


def _sdpa_ref(q, k, v, mask, key, *, scale, dropout_p, is_causal, window=None):
    # q,k,v: [batch, heads, seq, head_dim]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        if window is not None:
            # the band's lower edge: the query's own position and the
            # window - 1 before it
            causal &= jnp.triu(jnp.ones((s_q, s_k), bool), k=1 - window)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if not jnp.issubdtype(mask.dtype, jnp.floating):
            # bool/int keep-masks (reference converts via
            # _convert_attention_mask; adding raw 0/1 ints would bias
            # logits instead of masking)
            logits = jnp.where(mask.astype(bool), logits,
                               jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    # the softmax is float32 whatever q is (the amp O1 recipe)
    probs = (jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
             .astype(q.dtype))
    if dropout_p > 0.0 and key is not None:
        # counter-hash mask, not threefry bernoulli (core/random.py
        # fast_keep_mask): attention-prob masks dominate dropout RNG cost
        keep = random_core.fast_keep_mask(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def rotary(x, base=10000.0, positions=None, pairing="interleaved",
           rotary_dim=None, inv_freq=None, mscale=1.0):
    """Rotary embedding. x: [B, H, T, D]; positions: [T] absolute positions
    (defaults to 0..T-1). ``text/models.py``'s ``_rope``, shared with
    generation.py's cached decode.
    ``pairing``: which features rotate together — ``interleaved`` pairs
    (2i, 2i+1) (the Llama block here), ``half`` pairs (i, i + D/2) (the
    rotate-half form of the HF sources; OLMoE). The two differ by a fixed
    permutation of the columns of the q and k projections. ``rotary_dim``:
    only the first that many features rotate, among themselves (a partial
    rotary factor: Qwen3-Next turns 64 of 256); the rest pass.
    ``inv_freq`` (static: D/2 numbers) takes the place of the one table
    ``base ** (-2i/D)`` — a scaled RoPE's blended frequencies
    (``yarn_rope``) — and ``mscale`` multiplies cos and sin (YaRN's
    attention factor on the rotated features). A call that gives neither
    lowers to the program it always did."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [rotary(x[..., :rotary_dim], base, positions, pairing,
                    inv_freq=inv_freq, mscale=mscale),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    t = x.shape[-2]
    if positions is None:
        positions = jnp.arange(t)
    if inv_freq is None:
        inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
        if inv.shape != (d // 2,):
            raise ValueError(f"inv_freq holds {inv.shape} frequencies for "
                             f"{d} rotated features ({d // 2} pairs)")
    freqs = jnp.outer(positions, inv)

    def table(fn):
        values = fn(freqs) if mscale == 1.0 else fn(freqs) * mscale
        return values[None, None].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    if pairing == "half":
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    x1, x2 = x[..., ::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape)


def yarn_mscale(scale, mscale=1.0):
    """YaRN's magnitude correction for a context stretched ``scale`` times:
    ``0.1 mscale ln(scale) + 1`` (1 where nothing is stretched)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_rope(rope_scaling, dim, base=10000.0):
    """A ``rope_scaling`` group of ``type: yarn`` (HF ``DeepseekV3``'s
    reading of YaRN, arXiv:2309.00071) for ``dim`` rotated features ->
    (``inv_freq``: dim/2 floats, ``mscale`` for cos and sin, the factor on
    the softmax scale). The table blends ``base ** (-2i/dim)`` (kept where a
    feature turns more than ``beta_fast`` times over the original context)
    with the same over ``factor`` (where it turns fewer than ``beta_slow``
    times), by a linear ramp between the two correction dims, floor and
    ceiling taken. cos and sin carry ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``, and the softmax scale
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` (1 without
    ``mscale_all_dim``). Computed in float64 and rounded once."""
    import numpy as np

    kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling of type {kind!r}: only 'yarn' is "
                         f"built")
    factor = float(rope_scaling["factor"])
    original = rope_scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope_scaling.get("beta_fast", 32))),
              0)
    high = min(math.ceil(correction_dim(rope_scaling.get("beta_slow", 1))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq = plain / factor * ramp + plain * (1.0 - ramp)
    all_dim = rope_scaling.get("mscale_all_dim") or 0.0
    mscale = (yarn_mscale(factor, rope_scaling.get("mscale", 1.0))
              / yarn_mscale(factor, all_dim) if all_dim else
              yarn_mscale(factor))
    softmax = yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0
    return (tuple(float(v) for v in inv_freq.astype(np.float32)),
            float(mscale), float(softmax))


# ------------------------------------------------- the stage before the core
def qk_path(seq, heads, kv_heads, d, dtype, rotary_dim=None):
    """``kernel`` | ``xla`` for the stage before the core of a row of
    ``seq`` tokens with ``heads`` query heads on ``kv_heads`` key heads of
    ``d`` features in ``dtype``, the first ``rotary_dim`` of them rotated
    (None: none), from what can be observed: the Mosaic kernels where the
    program may hold them (``placement.kernel``: they run through
    ``on_mesh``), a head fills whole lane groups (d = 64 does not: two
    heads a lane group is a layout the core would have to share), the
    streams are bf16 or float32 and the row is at least one token block;
    where ``on_mesh`` cuts the heads over an 'mp' axis, only if that cuts
    both streams between whole heads. The XLA stage everything else."""
    from .pallas import qk_heads as kernels

    if not (seq >= kernels.QK_TOKENS
            and kernels.supported(heads, kv_heads, d, dtype, rotary_dim)
            and placement.kernel(sharded=True)):
        return "xla"
    mp = placement.axis_size("mp")
    return "xla" if heads % mp or kv_heads % mp else "kernel"


def qk_kernel(q, heads, kv_heads, d, rotary_dim=None):
    """One call's decision, counted, as ``qk_heads`` takes it:
    ``placement.kernel``'s answer where ``qk_path`` says ``kernel`` for a q
    stream like ``q``, else None (the XLA stage). An op's caller asks
    OUTSIDE the op; the answer rides its static arguments."""
    path = qk_path(q.shape[1], heads, kv_heads, d, q.dtype, rotary_dim)
    _QK_TOTAL.inc(path=path)
    return placement.kernel(sharded=True) if path == "kernel" else None


def qk_heads(q, k, w_q, w_k, *, heads, kv_heads, zero_centered, eps, rope,
             base=10000.0, rotary_dim=None, stride=1, kernel=None):
    """Softmax attention's stage between the projections and the core, on
    arrays: the projected streams q [B, T, heads x stride x d] and k [B, T,
    kv_heads x d] with their norms' weights [d] -> q [B, heads, T, d], k [B,
    kv_heads, T, d] in the streams' dtype. A head passes an RMSNorm over its
    d features in float32 (the weight, or 1 + weight where
    ``zero_centered``) and, where ``rope``, rotate-half RoPE in float32 on
    the first ``rotary_dim`` of them (None: all) at positions 0 .. T - 1;
    one rounding at the end. ``stride``: a query head's d features are the
    first of ``stride`` x d columns (Qwen3-Next lays a head out as [query |
    gate]: 2). ``kernel``: ``qk_kernel``'s answer, which the caller takes
    outside its dispatched op (None: the XLA stage)."""
    d = k.shape[-1] // kv_heads
    static = dict(heads=int(heads), kv_heads=int(kv_heads), d=d,
                  stride=int(stride), zero_centered=bool(zero_centered),
                  eps=float(eps), base=float(base), rotary_dim=(
                      (d if rotary_dim is None else int(rotary_dim))
                      if rope else None))
    if kernel is None:
        return _qk_xla(q, k, w_q, w_k, **static)
    return _qk_kernel(q, k, w_q, w_k, interpret=kernel == "interpret",
                      **static)


def _qk_xla(q, k, w_q, w_k, *, heads, kv_heads, d, stride, zero_centered,
            eps, base, rotary_dim):
    """The stage in XLA operations: float32 arrays as large as a stream in
    both the [.., T, heads, d] and the [.., heads, T, d] order, so a
    ``jax.checkpoint`` of its own — a differentiated program keeps the
    (bf16) streams and rebuilds the float32 inside it."""
    def stage(q, k, w_q, w_k):
        b, t, _ = q.shape
        if stride > 1:
            q = q.reshape(b, t, heads, stride * d)[..., :d]

        def normed(x, w, n):
            xf = x.reshape(b, t, n, d).astype(jnp.float32)
            wf = w.astype(jnp.float32)
            xf = (xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
                * (1.0 + wf if zero_centered else wf)).transpose(0, 2, 1, 3)
            if rotary_dim is not None:
                xf = rotary(xf, base, pairing="half", rotary_dim=rotary_dim)
            return xf.astype(x.dtype)

        return normed(q, w_q, heads), normed(k, w_k, kv_heads)

    return jax.checkpoint(stage)(q, k, w_q, w_k)


def _qk_kernel(q, k, w_q, w_k, *, heads, kv_heads, d, interpret, **static):
    """The Mosaic kernels, under a step's announced mesh inside
    ``placement.on_mesh``'s ``shard_map``: rows over the data axes and,
    where ``qk_path`` lets an ``mp`` axis through, both streams' heads over
    it (dim 2 in, dim 1 out). The weights go laid on every head's lanes a
    row ([B, 1, heads x d]), so that they cut as the streams do and their
    gradient is summed over rows and heads outside."""
    from .pallas import qk_heads as kernels

    def kernel(q, k, wq, wk):
        return kernels.qk_heads(q, k, wq, wk, d=d, interpret=interpret,
                                **static)

    def rows(w, n):
        return jnp.broadcast_to(jnp.tile(w, n)[None, None],
                                (q.shape[0], 1, n * d))

    return tuple(placement.on_mesh(
        kernel, (q, k, rows(w_q, heads), rows(w_k, kv_heads)), head_axis=2,
        out_head_axis=1))


def attention_route(*, batch, seq_q, seq_k, num_heads, head_dim, dtype,
                    packed, masked, is_causal, window=None):
    """``short`` | ``stream`` | ``xla`` for one attention call.
    ``packed`` says the caller holds the fused [batch, seq, 3*embed]
    projection (so it is self-attention and seq_q == seq_k). ``window``
    (static; causal self-attention only) changes no decision: the kernel's
    saving grows with the keys a band leaves out, XLA's S^2 buffer does not
    shrink with them."""
    if window is not None and (not is_causal or seq_q != seq_k
                               or window < 1):
        raise ValueError(
            f"a window ({window}) takes causal attention of queries on as "
            f"many keys (is_causal={is_causal}, {seq_q} on {seq_k})")
    # ``stream`` asks as a site with no XLA way out (placement.kernel)
    if masked or not placement.kernel(sharded=True, no_fallback=True):
        return "xla"
    from .pallas import flash_attention

    # the short kernel's grid is the batch in row blocks: a batch that is
    # a symbol (jit.save's batch-polymorphic export) has no divisors.
    # Where it cannot be placed XLA's route serves (its S^2 buffers are
    # small at these lengths)
    if (packed and not is_causal and isinstance(batch, int)
            and flash_attention.short_supported(
                seq_q, num_heads, head_dim, dtype)
            and placement.kernel(sharded=True)):
        return "short"
    # kernel overhead is governed by seq_k (the per-program inner-loop
    # length), XLA's memory blowup by the seq_q*seq_k logits buffer. So:
    # stream when the k side is long, or when the logits are as big as a
    # min_seq^2 square AND k is at least two blocks long (long-q/short-k
    # stays on XLA: its logits are small and the kernel would run one k
    # block per program). 0 = always the kernel.
    min_seq = flags.flag_value("pallas_attention_min_seq")
    if seq_k >= min_seq or (seq_k >= _STREAM_MIN_KEYS
                            and seq_q * seq_k >= min_seq * min_seq):
        return "stream"
    return "xla"


def _kernel_seed(key):
    """The kernels' 32-bit dropout seed from a PRNG key: every key word
    is folded in (w0 ^ fmix32(w1 ^ fmix32(w2 ...))), not the last word
    alone — keys that differ in any word give different masks."""
    if key is None:
        return jnp.zeros((), jnp.int32)
    words = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    seed = words[-1]
    for i in range(words.shape[0] - 2, -1, -1):
        seed = words[i] ^ random_core.fmix32(seed)
    return jax.lax.bitcast_convert_type(seed, jnp.int32)


def _flash(q, k, v, key, *, scale, is_causal, dropout_p, interpret,
           window=None):
    """The streaming kernel on [batch, heads, seq, head_dim]; with a
    ``window`` its banded calls."""
    from .pallas import flash_attention

    kernel = functools.partial(
        flash_attention.mha, scale=scale, causal=is_causal,
        dropout_p=dropout_p, interpret=interpret, window=window)
    return placement.on_mesh(kernel, (q, k, v), head_axis=1,
                             seed=_kernel_seed(key), seed_per_shard=True)


def _short(qkv, key, *, num_heads, scale, dropout_p, interpret):
    """The whole-sequence kernel on packed [batch, seq, 3*embed]; under a
    mesh the batch shards over the data axes. The rows carry their numbers
    in the whole batch, which is what the mask hashes: one seed serves
    every shard, and the mask does not depend on the mesh."""
    from .pallas import flash_attention

    def kernel(qkv, row_ids, seed):
        return flash_attention.mha_packed(
            qkv, num_heads, scale=scale, dropout_p=dropout_p, seed=seed,
            row_ids=row_ids, interpret=interpret)

    rows = jnp.arange(qkv.shape[0], dtype=jnp.int32)
    return placement.on_mesh(kernel, (qkv, rows), head_axis=None,
                             seed=_kernel_seed(key))


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True,
                                 window=None, scale=None):
    """Attention on [batch, heads, seq, head_dim] Tensors by the route
    ``attention_route`` picks (``stream``: the streaming flash kernel, with
    a window its banded calls; else ``xla``: ``_sdpa_ref``), counted in
    ``paddle_tpu_attention_route_total{route}``. ``scale`` multiplies the
    scores before the softmax on every route; None is ``head_dim ** -0.5``
    (Granite's ``attention_multiplier`` is 1/64 at heads of 64, not 1/8).
    ``window`` (static; with ``is_causal``, queries on as many keys): a
    sliding window of that many keys up to the query's own position; None,
    or one that reaches every key, is full attention. A call that gives
    neither lowers to the program it always did."""
    head_dim = q.shape[-1]
    scale = 1.0 / math.sqrt(head_dim) if scale is None else float(scale)
    p = float(dropout_p) if training else 0.0
    route = attention_route(
        batch=q.shape[0], seq_q=q.shape[-2], seq_k=k.shape[-2],
        num_heads=q.shape[-3], head_dim=head_dim, dtype=q.dtype,
        packed=False, masked=attn_mask is not None,
        is_causal=bool(is_causal), window=window)
    _ROUTE_TOTAL.inc(route=route)
    # the static kwargs of a call without a window stay as they were (the
    # per-(op, shape) dispatch cache keys on them)
    windowed = {}
    if window is not None and window < k.shape[-2]:
        _WINDOW_ROUTE_TOTAL.inc(route=route)
        windowed["window"] = int(window)
    key = random_core.next_key() if p > 0.0 else None
    if route == "stream":
        # interpret rides the static kwargs so a flag flip retraces
        return apply_op(
            "flash_attention", _flash, q, k, v, key,
            scale=scale, is_causal=bool(is_causal), dropout_p=p,
            interpret=placement.kernel(sharded=True) == "interpret",
            **windowed)

    return apply_op(
        "sdpa", _sdpa_ref, q, k, v, attn_mask, key,
        scale=scale, dropout_p=p, is_causal=bool(is_causal), **windowed)


def packed_self_attention(qkv, num_heads, attn_mask=None, dropout_p=0.0,
                          is_causal=False, training=True):
    """Self-attention on the fused projection's output. qkv: [batch, seq,
    3*embed], q | k | v along the last axis; returns [batch, seq, embed]
    with the heads merged. Route ``short`` reads and writes those layouts
    in place; any other route splits the heads, calls
    ``scaled_dot_product_attention`` and merges them again."""
    batch, seq, embed3 = qkv.shape
    embed = embed3 // 3
    head_dim = embed // num_heads
    route = attention_route(
        batch=batch, seq_q=seq, seq_k=seq, num_heads=num_heads,
        head_dim=head_dim, dtype=qkv.dtype, packed=True,
        masked=attn_mask is not None, is_causal=bool(is_causal))
    if route == "short":
        _ROUTE_TOTAL.inc(route=route)
        p = float(dropout_p) if training else 0.0
        key = random_core.next_key() if p > 0.0 else None
        return apply_op(
            "short_attention", _short, qkv, key, num_heads=int(num_heads),
            scale=1.0 / math.sqrt(head_dim), dropout_p=p,
            interpret=placement.kernel(sharded=True) == "interpret")

    from .. import tensor as pt

    def split_heads(x):
        x = pt.reshape(x, [batch, seq, num_heads, head_dim])
        return pt.transpose(x, [0, 2, 1, 3])

    q, k, v = (split_heads(x) for x in pt.split(qkv, 3, axis=-1))
    out = scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training)
    return pt.reshape(pt.transpose(out, [0, 2, 1, 3]), [batch, seq, embed])
