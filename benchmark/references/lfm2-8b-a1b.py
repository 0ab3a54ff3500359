"""Plain float32 forward and loss of LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B, HF
``lfm2_moe``), in straightforward ``jax.numpy`` with no framework, kernel,
sort or cache: the short convolution is three explicit shifted multiply-adds,
attention a softmax over every key under an explicit [queries, keys] mask,
in blocks of queries, every held expert runs on every token and a [tokens,
experts] weight matrix that is zero outside a token's top-k selects.
Weights (and the routers' bias buffers) come as a dict under the framework's
names; Linear weights are [in, out], the held experts' are stacked [held,
in, out], the convolution's taps [3, channels].

rms(x; w) = x rsqrt(mean x^2 + eps) w                       (w from 1)
h0 = E[ids]                                                 (no multiplier)
A block, on h [n, s, hidden]:
  a  = h + Op(rms(h; operator_norm))
  h' = a + FFN(rms(a; ffn_norm))
The layers run are ``run_layers`` of the published ``layer_types``.

``conv`` operator (the doubly gated short convolution):
  [B | C | u] = x W_in (hidden -> 3 x hidden, split in that order);
  g = B * u;  c_t = w_2 g_t + w_1 g_{t-1} + w_0 g_{t-2}  — causal, one
  filter a channel (tap 2 meets the token itself, as torch's Conv1d with
  padding 2 cut to the row's length), ZERO history before a row's first
  token: row r's first two tokens never see row r - 1; no bias, no
  activation;  y = (C * c) W_out.

``full_attention`` operator (H query heads on H_kv key/value heads of d =
hidden / H):
  q = rms_d(x W_q) a head (q_layernorm), k = rms_d(x W_k) a head
  (k_layernorm), v = x W_v; q and k take rotate-half RoPE over all d
  features at ``rope_theta``; key j is visible to query i iff j <= i;
  o = softmax(q k^T d^-0.5 + mask) v with query head h on key/value head
  h // (H / H_kv);  y = concat(o) W_o

FFN: dense SwiGLU W_2(silu(W_1 x) * W_3 x) (gate_proj, up_proj, down_proj)
  in the first ``num_dense_layers`` blocks; elsewhere
  s = sigmoid(m W_r) over ALL ``router_experts``; the choice is top-k of
  s + b (b: ``e_score_correction_bias``, HF's ``expert_bias``, no gradient),
  the weights are s at the chosen experts / (their sum + 1e-6) x
  ``routed_scaling_factor``;
  out = sum over the chosen experts THAT ARE HELD HERE (``held_experts`` =
  [first, count]) of w_e expert_e(m), every expert a SwiGLU; no shared
  expert. What the absent experts would have added is left out, as in the
  program; with every expert held this is the whole layer.
logits = rms(h; embedding_norm) E^T                         (the head is tied)

Loss = CE(logits_i, t_{i+1}) over the step's tokens; the router is balanced
by its bias alone, no auxiliary term. After a step b += rate x sign(mean
load - load) (``bias_update``).

The held share's row bound is the program's: at most ``held_rows(N)``
(token, choice) pairs a layer, taken in (expert, token, choice) order, the
rest dropped and counted (0 at every size checked).

Every caller runs this under ``jax.default_matmul_precision("highest")``
(the entry points set it; ``precision=None`` leaves the platform's default,
which is how the check shows that a lower precision fails its tolerance).
"""
import contextlib
import math

import jax
import jax.numpy as jnp

RENORM_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def layer_types(sizes):
    """The types of the layers run: published layer i for i in
    ``run_layers``."""
    return [sizes["layer_types"][i] for i in sizes["run_layers"]]


def held_rows(tokens, sizes):
    first, count = sizes["held_experts"]
    mean = tokens * sizes["num_experts_per_tok"] * count / sizes[
        "router_experts"]
    rows = -(-math.ceil(sizes["held_rows_factor"] * mean) // 512) * 512
    return min(rows, tokens * sizes["num_experts_per_tok"])


def rope(x, theta):
    """Rotate-half RoPE over all features of x [n, h, s, d]."""
    s, d = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.outer(jnp.arange(s), inv)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def shifted(g, by):
    """g [n, s, c] moved ``by`` tokens later in its own row, zeros in
    front: position t holds g_{t - by}."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:, :by]), g[:, :-by]], axis=1)


def short_conv(w, a, sizes, p):
    """The conv operator on normed input a [n, s, hidden]."""
    hidden = a.shape[-1]
    bcu = a @ w[p + "in_proj.weight"]
    b, c, u = (bcu[..., :hidden], bcu[..., hidden:2 * hidden],
               bcu[..., 2 * hidden:])
    g = b * u
    taps = w[p + "conv.weight"]                 # [3, hidden]; tap 2 meets t
    k = taps.shape[0]
    mixed = sum(taps[k - 1 - by] * shifted(g, by) for by in range(k))
    return (c * mixed) @ w[p + "out_proj.weight"]


def attention(w, a, sizes, p):
    """The full_attention operator on normed input a [n, s, hidden]."""
    n, s, hidden = a.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = hidden // heads, sizes["norm_eps"]
    group = heads // kv
    q = _rms((a @ w[p + "q_proj.weight"]).reshape(n, s, heads, d),
             w[p + "q_layernorm.weight"], eps).transpose(0, 2, 1, 3)
    k = _rms((a @ w[p + "k_proj.weight"]).reshape(n, s, kv, d),
             w[p + "k_layernorm.weight"], eps).transpose(0, 2, 1, 3)
    v = (a @ w[p + "v_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    q = q.reshape(n, kv, group, s, d)      # query head h on kv head h // group

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = jnp.einsum("bkgqd,bksd->bkgqs", q_rows, k) * d ** -0.5
        i = first + jnp.arange(q_rows.shape[3])
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # in blocks of queries where the [rows, heads, s, s] scores would not
    # fit (4 rows of 8,192 positions: 34 GB); each block meets every key
    # under its mask
    qb = sizes.get("reference_q_block", 256)
    if s > qb and s % qb == 0:
        blocks = q.reshape(n, kv, group, s // qb, qb, d)
        o = jax.lax.map(lambda i: rows(blocks[:, :, :, i], i * qb),
                        jnp.arange(s // qb))
        o = jnp.moveaxis(o, 0, 3).reshape(n, heads, s, d)
    else:
        o = rows(q, 0).reshape(n, heads, s, d)
    o = o.transpose(0, 2, 1, 3).reshape(n, s, heads * d)
    return o @ w[p + "out_proj.weight"]


def experts(w, m, sizes, p):
    """The expert sublayer on normed tokens m [N, hidden]: (output, each
    token's router margin — the gap between its k-th and (k + 1)-th biased
    score —, pairs dropped, pairs that landed on the held experts, the
    load of every expert [router_experts])."""
    tokens = m.shape[0]
    top_k, total = sizes["num_experts_per_tok"], sizes["router_experts"]
    first, count = sizes["held_experts"]
    s = jax.nn.sigmoid(m @ w[p + "gate.weight"])              # [N, E]
    bias = w.get(p + "e_score_correction_bias")
    biased = s if bias is None else s + bias
    ranked, idx = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k + 1)
    idx = idx[:, :top_k]
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = jnp.sum(jax.nn.one_hot(idx, total, dtype=s.dtype), axis=1)
    weights = s * chosen
    if sizes.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + RENORM_EPS)
    weights = weights * sizes["routed_scaling_factor"]
    # the row bound: held pairs in (expert, token, choice) order; a pair
    # whose rank reaches ``held_rows`` is dropped
    here = chosen[:, first:first + count]                     # [N, held]
    per_expert = jnp.sum(here, axis=0)
    rank = (jnp.cumsum(per_expert) - per_expert)[None, :] + (
        jnp.cumsum(here, axis=0) - here)
    kept = here * (rank < held_rows(tokens, sizes))
    dropped = jnp.sum(here) - jnp.sum(kept)
    held_weights = weights[:, first:first + count] * kept

    def one(acc, xs):
        w_gate, w_up, w_down, weight = xs
        return acc + weight[:, None] * _swiglu(m, w_gate, w_up, w_down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        w[p + "w_gate"], w[p + "w_up"], w[p + "w_down"], held_weights.T))
    return routed, margin, dropped, jnp.sum(here), jnp.sum(chosen, axis=0)


def bias_update(bias, load, rate):
    """The selection bias after a step: up where an expert took less than
    the mean load, down where more."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def block(w, h, sizes, p, dense, mixer):
    """One decoder block on h [n, s, hidden] under the parameter prefix p:
    (output, 0 — there is no balancing term —, router margins [n, s], pairs
    dropped, pairs that landed here)."""
    n, s, hidden = h.shape
    eps = sizes["norm_eps"]
    a = _rms(h, w[p + "operator_norm.weight"], eps)
    if mixer == "conv":
        h = h + short_conv(w, a, sizes, p + "conv.")
    else:
        h = h + attention(w, a, sizes, p + "self_attn.")
    m = _rms(h, w[p + "ffn_norm.weight"], eps)
    p = p + "feed_forward."
    if dense:
        y = _swiglu(m, w[p + "gate_proj.weight"], w[p + "up_proj.weight"],
                    w[p + "down_proj.weight"])
        return h + y, 0.0, jnp.full((n, s), jnp.inf), 0.0, None
    y, margin, dropped, landed, _ = experts(
        w, m.reshape(n * s, hidden), sizes, p)
    return (h + y.reshape(n, s, hidden), 0.0, margin.reshape(n, s), dropped,
            landed)


def _forward(w, input_ids, sizes, prefix):
    embedding = w[prefix + "embed_tokens.weight"]
    h = embedding[input_ids]
    margins, dropped, landed = [], 0.0, []
    for i, mixer in enumerate(layer_types(sizes)):
        h, _, m, d, n_here = block(w, h, sizes, f"{prefix}layers.{i}.",
                                   i < sizes["num_dense_layers"], mixer)
        dropped = dropped + d
        margins.append(m)
        landed += [] if n_here is None else [n_here]
    logits = _rms(h, w[prefix + "embedding_norm.weight"],
                  sizes["norm_eps"]) @ embedding.T
    return logits, jnp.min(jnp.stack(margins), axis=0), dropped, landed


def _precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


def _f32(w):
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def forward(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s] token ids -> the logits [n, s, vocab]."""
    with _precision(precision):
        return _forward(_f32(w), input_ids, sizes, prefix)[0]


def outputs(w, input_ids, sizes, prefix="", precision="highest"):
    """One pass: (logits, total loss, cross-entropy, the balancing term — 0
    —, pairs dropped, router margins, pairs that landed on the held experts
    in each expert block). Position i's label is token i + 1; a row's last
    position predicts nothing."""
    with _precision(precision):
        logits, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1:, None], axis=-1))
        return logits, ce, ce, 0.0, dropped, margin, landed


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, cross-entropy, balancing term, pairs dropped)."""
    return outputs(w, input_ids, sizes, prefix, precision)[1:5]
