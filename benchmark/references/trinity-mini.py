"""Plain float32 forward and loss of Trinity-Mini (arcee-ai/Trinity-Mini, HF
``afmoe``), in straightforward ``jax.numpy`` with no framework, kernel, band
of blocks, sort or cache: attention is a softmax over every key under an
explicit [queries, keys] mask, in blocks of queries, every held expert runs
on every token and a [tokens, experts] weight matrix that is zero outside a
token's top-k selects. Weights (and the routers' bias buffers) come as a
dict under the framework's names; Linear weights are [in, out], the held
experts' are stacked [held, in, out].

rms(x; w) = x rsqrt(mean x^2 + eps) w                       (w from 1)
h0 = E[ids] sqrt(hidden_size)                               (``mup_enabled``)
A block, on h [n, s, hidden] (a norm before AND after each sublayer):
  a  = h + rms(Attn(rms(h; input_layernorm)); post_attention_layernorm)
  h' = a + rms(MLP(rms(a; pre_mlp_layernorm)); post_mlp_layernorm)
The layers run are ``run_layers`` of the published ``layer_types``.

Attention (H query heads on H_kv key/value heads of d):
  q = rms_d(x W_q) a head (q_norm), k = rms_d(x W_k) a head (k_norm),
  v = x W_v, g = x W_g (``gate_proj``, a matrix of its own);
  ``sliding_attention``: q and k take rotate-half RoPE over all d features
  at ``rope_theta``; key j is visible to query i iff i - sliding_window < j
  <= i (the query's own position and the sliding_window - 1 before it);
  ``full_attention``: NOTHING is rotated; key j is visible iff j <= i;
  o = softmax(q k^T d^-0.5 + mask) v with query head h on key/value head
  h // (H / H_kv);  y = (concat(o) * sigmoid(g)) W_o

MLP: dense SwiGLU in the first ``num_dense_layers`` blocks; elsewhere
  s = sigmoid(m W_r) over ALL ``router_experts``; the choice is top-k of
  s + b (b: ``e_score_correction_bias``, no gradient), the weights are s at
  the chosen experts / (their sum + 1e-20) x ``route_scale``;
  out = shared(m) + sum over the chosen experts THAT ARE HELD HERE
  (``held_experts`` = [first, count]) of w_e expert_e(m), every expert and
  the shared one W_down(silu(W_gate m) * W_up m). What the absent experts
  would have added is left out, as in the program; with every expert held
  this is the whole layer.
logits = rms(h; norm) W_head.

Loss = CE(logits_i, t_{i+1}) over the step's tokens; the router is balanced
by its bias alone (``load_balance_coeff`` is the bias's rate), no auxiliary
term. After a step b += rate x sign(mean load - load) (``bias_update``).

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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(m, w, p):
    return (jax.nn.silu(m @ w[p + "gate_proj.weight"])
            * (m @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


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


def visible(queries, keys, window):
    """[queries, keys] mask: key j is visible to query i iff j <= i and,
    under a window, i - window < j."""
    i, j = queries[:, None], keys[None, :]
    seen = j <= i
    return seen if window is None else seen & (j > i - window)


def attention(w, a, sizes, p, mixer):
    """The attention sublayer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    group = heads // kv
    sliding = mixer == "sliding_attention"
    window = sizes["sliding_window"] if sliding else None
    q = _rms((a @ w[p + "q_proj.weight"]).reshape(n, s, heads, d),
             w[p + "q_norm.weight"], eps).transpose(0, 2, 1, 3)
    k = _rms((a @ w[p + "k_proj.weight"]).reshape(n, s, kv, d),
             w[p + "k_norm.weight"], eps).transpose(0, 2, 1, 3)
    v = (a @ w[p + "v_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    gate = a @ w[p + "gate_proj.weight"]
    if sliding:
        q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])
    q = q.reshape(n, kv, group, s, d)      # query head h on kv head h // group

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = jnp.einsum("bkgqd,bksd->bkgqs", q_rows, k) * d ** -0.5
        seen = visible(first + jnp.arange(q_rows.shape[3]), jnp.arange(s),
                       window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # in blocks of queries where the [heads, s, s] scores would not fit
    # (16,384 positions: 34 GB); each block meets every key under its mask
    qb = sizes.get("reference_q_block", 256)
    if s > qb and s % qb == 0:
        blocks = q.reshape(n, kv, group, s // qb, qb, d)
        o = jax.lax.map(lambda i: rows(blocks[:, :, :, i], i * qb),
                        jnp.arange(s // qb))
        o = jnp.moveaxis(o, 0, 3).reshape(n, heads, s, d)
    else:
        o = rows(q, 0).reshape(n, heads, s, d)
    o = o.transpose(0, 2, 1, 3).reshape(n, s, heads * d)
    return (o * jax.nn.sigmoid(gate)) @ w[p + "o_proj.weight"]


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
    if sizes.get("route_norm", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    weights = weights * sizes["route_scale"]
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
        y = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return acc + weight[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        w[p + "w_gate"], w[p + "w_up"], w[p + "w_down"], held_weights.T))
    out = routed + _swiglu(m, w, p + "shared.")
    return out, margin, dropped, jnp.sum(here), jnp.sum(chosen, axis=0)


def bias_update(bias, load, rate):
    """The selection bias after a step: up where an expert took less than
    the mean load, down where more."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def block(w, h, sizes, p, dense, mixer):
    """One decoder block on h [n, s, hidden] under the parameter prefix p:
    (output, 0 — there is no balancing term —, router margins [n, s], pairs
    dropped, pairs that landed here)."""
    n, s, hidden = h.shape
    eps = sizes["rms_norm_eps"]
    a = attention(w, _rms(h, w[p + "input_layernorm.weight"], eps), sizes,
                  p + "self_attn.", mixer)
    h = h + _rms(a, w[p + "post_attention_layernorm.weight"], eps)
    m = _rms(h, w[p + "pre_mlp_layernorm.weight"], eps)
    post = w[p + "post_mlp_layernorm.weight"]
    if dense:
        return (h + _rms(_swiglu(m, w, p + "mlp."), post, eps), 0.0,
                jnp.full((n, s), jnp.inf), 0.0, None)
    y, margin, dropped, landed, _ = experts(
        w, m.reshape(n * s, hidden), sizes, p + "mlp.")
    return (h + _rms(y.reshape(n, s, hidden), post, eps), 0.0,
            margin.reshape(n, s), dropped, landed)


def _forward(w, input_ids, sizes, prefix):
    h = w[prefix + "embed_tokens.weight"][input_ids]
    if sizes.get("mup_enabled", True):
        h = h * math.sqrt(sizes["hidden_size"])
    margins, dropped, landed = [], 0.0, []
    for i, mixer in enumerate(layer_types(sizes)):
        h, _, m, d, n_here = block(w, h, sizes, f"{prefix}layers.{i}.",
                                   i < sizes["num_dense_layers"], mixer)
        dropped = dropped + d
        margins.append(m)
        landed += [] if n_here is None else [n_here]
    logits = _rms(h, w[prefix + "norm.weight"],
                  sizes["rms_norm_eps"]) @ w[prefix + "lm_head.weight"]
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
