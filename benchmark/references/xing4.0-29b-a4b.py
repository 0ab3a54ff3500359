"""Plain float32 forward and loss of Xing4.0-29B-A4B (HF ``xing4_0``): the
DeepSeek-V3 family's decoder (technical report arXiv:2412.19437, sections
2.1-2.2: latent attention, a sigmoid router with a selection bias, a shared
expert, a multi-token-prediction module) on MANIFOLD-CONSTRAINED
HYPER-CONNECTIONS (mHC, DeepSeek-AI, arXiv:2512.24880, on Hyper-Connections,
arXiv:2409.19606: the config's ``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps``, ``mhc_h_res_clamp_min/max``), with YaRN frequencies
(arXiv:2309.00071, as HF ``DeepseekV3`` reads ``rope_scaling``). In
straightforward ``jax.numpy`` with no framework, kernel, sort or cache; it
imports nothing from ``paddle_tpu``. Weights (and the routers' bias buffers)
come as a dict under the framework's names; Linear weights are [in, out],
the held experts' are stacked [held, in, out].

The residual path. n = ``hc_mult`` streams a token, X [.., n, C]; after the
embedding X = [e; e; ..; e]. Around each sublayer F (latent attention behind
``input_layernorm``: parameters ``attn_hc.*``; then the dense SwiGLU or the
expert layer behind ``post_attention_layernorm``: ``mlp_hc.*``), with phi
[n C, 2 n + n^2], b [2 n + n^2], alpha (pre, post, res):
  x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)          (no weight)
  [h_pre | h_post | h_res] = x~ phi                          (n, n, n^2)
  H_pre  = sigmoid(alpha_pre h_pre + b_pre)                  [n]
  H_post = 2 sigmoid(alpha_post h_post + b_post)             [n]
  H_res  = SinkhornKnopp(clamp(alpha_res mat(h_res) + b_res, min, max)):
           M = exp(.), then ``hc_sinkhorn_iters`` rounds of (each row over
           its sum + hc_eps, each column over its sum + hc_eps) — all of
           them, no test for convergence                     [n, n]
  u = H_pre X;  y = F(norm(u));  X <- H_res X + H_post^T y
After the last block h = sum_i X_i, then the final norm and the head.

A sublayer F, on u [n, s, hidden]:
  latent attention: c_q = rmsnorm(a W_qa); q = c_q W_qb -> heads x (nope +
  rope); [c_kv ; k_r] = a W_kva; c_kv = rmsnorm(c_kv); [k_nope ; v] = c_kv
  W_kvb; RoPE (pairs (2i, 2i + 1)) on each head's q_rope and on the ONE k_r
  all heads share, by YaRN's table: inv_freq_i = base^(-2i/d) blended with
  the same / factor by the linear ramp between the correction dims of
  beta_fast and beta_slow over the original positions (floor, ceiling); cos
  and sin times yarn_mscale(factor, mscale) / yarn_mscale(factor,
  mscale_all_dim); o = softmax(q k^T (nope + rope)^-0.5 yarn_mscale(factor,
  mscale_all_dim)^2 + causal) v; y = concat(o) W_o. The heads are the ones
  HELD here (``num_attention_heads`` of the weights given): W_o gives their
  partial sum.
  dense: y = W_down(silu(m W_gate) * (m W_up)).
  expert: s = sigmoid(m W_r) over ALL ``router_experts``; top-k of s + b,
  weights s at the chosen / their sum x ``routed_scaling_factor``; y =
  shared(m) + sum over the chosen experts THAT ARE HELD HERE of w_e
  expert_e(m), under the held share's row bound (``held_rows``).

MTP module (one): x = W_eh [rmsnorm_h(h) ; rmsnorm_e(Emb(t shifted left by
d + 1))] with h the REDUCED state (the streams' sum, before the final norm)
-> one expert block that replicates x to n streams of its own and sums them
at its end -> rmsnorm -> the same head.

Loss = CE(main_i, t_{i+1}) + ``mtp_loss_weight`` x CE(mtp_i, t_{i+2}) +
``balance_loss_weight`` x sum over expert layers of E sum_e (n_e / N)
mean_t s'[t, e].

Every caller runs this under ``jax.default_matmul_precision("highest")``
(the entry points set it; ``precision=None`` leaves the platform's default,
which is how the check shows that a lower precision fails its tolerance).
"""
import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


# ------------------------------------------------------------------ YaRN
def yarn_mscale(scale, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, base, scaling):
    """The dim/2 blended frequencies (float64)."""
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def rope_tables(sizes):
    """(inv_freq [rope/2], the factor on cos and sin, the softmax scale)."""
    rope, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    plain_scale = (sizes["qk_nope_head_dim"] + rope) ** -0.5
    scaling = sizes.get("rope_scaling")
    if not scaling:
        return (base ** (-np.arange(0, rope, 2, dtype=np.float64) / rope),
                1.0, plain_scale)
    factor, all_dim = scaling["factor"], scaling.get("mscale_all_dim", 0)
    on_rotation = (yarn_mscale(factor, scaling.get("mscale", 1))
                   / yarn_mscale(factor, all_dim) if all_dim
                   else yarn_mscale(factor))
    on_softmax = yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0
    return (yarn_inv_freq(rope, base, scaling), on_rotation,
            plain_scale * on_softmax)


def _rope(x, inv_freq, factor):
    """x: [..., s, d]; interleaved pairing (2i, 2i + 1)."""
    s = x.shape[-2]
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


# ------------------------------------------------------------- sublayers
def _swiglu(m, w, p):
    return (jax.nn.silu(m @ w[p + "gate_proj.weight"])
            * (m @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


def held_rows(tokens, sizes):
    first, count = sizes["held_experts"]
    mean = tokens * sizes["num_experts_per_tok"] * count / sizes[
        "router_experts"]
    rows = -(-math.ceil(sizes["held_rows_factor"] * mean) // 512) * 512
    return min(rows, tokens * sizes["num_experts_per_tok"])


def attention(w, a, sizes, p):
    """The latent-attention sublayer on normed input a [n, s, hidden]: the
    partial sum over the heads whose weights are given."""
    n, s, _ = a.shape
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    eps = sizes["rms_norm_eps"]
    inv_freq, factor, scale = rope_tables(sizes)
    c_q = _rms_norm(a @ w[p + "q_a_proj.weight"],
                    w[p + "q_a_layernorm.weight"], eps)
    q = (c_q @ w[p + "q_b_proj.weight"]).reshape(
        n, s, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = a @ w[p + "kv_a_proj_with_mqa.weight"]
    c_kv = _rms_norm(kv_a[..., :rank], w[p + "kv_a_layernorm.weight"], eps)
    k_r = _rope(kv_a[..., rank:], inv_freq, factor)           # [n, s, rope]
    kv = (c_kv @ w[p + "kv_b_proj.weight"]).reshape(
        n, s, heads, nope + dv).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], inv_freq, factor)], axis=-1)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q_rows[..., :nope], k_nope)
                  + jnp.einsum("bhqd,bkd->bhqk", q_rows[..., nope:], k_r)
                  ) * scale
        at = first + jnp.arange(q_rows.shape[2])
        causal = at[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    # in blocks of queries where the [heads, s, s] scores would not fit;
    # each block sees every key
    qb = sizes.get("reference_q_block", 1024)
    if s > qb and s % qb == 0:
        blocks = q.reshape(n, heads, s // qb, qb, nope + rope)
        o = jax.lax.map(lambda i: rows(blocks[:, :, i], i * qb),
                        jnp.arange(s // qb))
        o = jnp.moveaxis(o, 0, 2).reshape(n, heads, s, dv)
    else:
        o = rows(q, 0)
    return o.transpose(0, 2, 1, 3).reshape(n, s, heads * dv) @ w[
        p + "o_proj.weight"]


def experts(w, m, sizes, p):
    """The expert sublayer on normed tokens m [N, hidden]: (output, the
    balancing term, each token's router margin, pairs dropped, pairs that
    landed on the held experts)."""
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
                             + 1e-20)
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
        y = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return acc + weight[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        w[p + "w_gate"], w[p + "w_up"], w[p + "w_down"], held_weights.T))
    out = routed + _swiglu(m, w, p + "shared.")
    normalised = s / jnp.sum(s, axis=-1, keepdims=True)
    balance = total * jnp.sum(jnp.sum(chosen, axis=0) / tokens
                              * jnp.mean(normalised, axis=0))
    return out, balance, margin, dropped, jnp.sum(here)


# ------------------------------------------------------ the residual path
def sinkhorn_knopp(logits, sizes):
    """[.., n, n] -> exp of the clamped logits, then every one of
    ``hc_sinkhorn_iters`` rounds (rows, then columns)."""
    m = jnp.exp(jnp.clip(logits, sizes["mhc_h_res_clamp_min"],
                         sizes["mhc_h_res_clamp_max"]))
    for _ in range(sizes["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + sizes["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + sizes["hc_eps"])
    return m


def hyper_maps(w, X, sizes, p):
    """X [.., n, C] -> (H_pre [.., n], H_post [.., n], H_res [.., n, n])."""
    n = sizes["hc_mult"]
    vec = X.reshape(*X.shape[:-2], -1)
    x = vec * jax.lax.rsqrt(jnp.mean(vec * vec, axis=-1, keepdims=True)
                            + sizes["rms_norm_eps"])
    h, alpha, b = x @ w[p + "phi"], w[p + "alpha"], w[p + "b"]
    pre = alpha[0] * h[..., :n] + b[:n]
    post = alpha[1] * h[..., n:2 * n] + b[n:2 * n]
    res = (alpha[2] * h[..., 2 * n:] + b[2 * n:]).reshape(
        *h.shape[:-1], n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn_knopp(res, sizes))


def hyper_sublayer(w, X, sizes, p, fn):
    """``X <- H_res X + H_post^T fn(H_pre X)`` with the maps under ``p``;
    fn returns (y, extras)."""
    h_pre, h_post, h_res = hyper_maps(w, X, sizes, p)
    u = jnp.einsum("...n,...nc->...c", h_pre, X)
    y, extras = fn(u)
    return (jnp.einsum("...ij,...jc->...ic", h_res, X)
            + h_post[..., :, None] * y[..., None, :]), extras


def expand(h, sizes):
    """[n, s, C] -> the streams [n, s, hc_mult, C], each a copy."""
    return jnp.repeat(h[..., None, :], sizes["hc_mult"], axis=-2)


def reduce(X):
    return jnp.sum(X, axis=-2)


def block(w, X, sizes, p, dense):
    """One decoder block on the streams X [n, s, hc_mult, C] under the
    parameter prefix p: (the streams after it, balancing term, router
    margins [n, s], pairs dropped, pairs that landed here)."""
    n, s, _, hidden = X.shape
    eps = sizes["rms_norm_eps"]
    X, _ = hyper_sublayer(w, X, sizes, p + "attn_hc.", lambda u: (attention(
        w, _rms_norm(u, w[p + "input_layernorm.weight"], eps), sizes,
        p + "self_attn."), None))

    def feed_forward(u):
        m = _rms_norm(u, w[p + "post_attention_layernorm.weight"], eps)
        if dense:
            return _swiglu(m, w, p + "mlp."), (
                0.0, jnp.full((n, s), jnp.inf), 0.0, None)
        y, balance, margin, dropped, landed = experts(
            w, m.reshape(n * s, hidden), sizes, p + "mlp.")
        return y.reshape(n, s, hidden), (balance, margin.reshape(n, s),
                                         dropped, landed)

    X, extras = hyper_sublayer(w, X, sizes, p + "mlp_hc.", feed_forward)
    return (X,) + extras


def mtp_block(w, x, sizes, p):
    """The MTP module's block on ONE hidden state x [n, s, C]: streams of
    its own, an expert block, their sum."""
    X, balance, margin, dropped, landed = block(w, expand(x, sizes), sizes,
                                                p, False)
    return reduce(X), balance, margin, dropped, landed


def embed(w, ids, prefix=""):
    return w[prefix + "embed_tokens.weight"][ids]


def mtp_input(w, h_prev, ids, sizes, p, prefix=""):
    """What MTP module p feeds its block: h_prev is the (reduced) state it
    reads, ids the tokens ALREADY shifted for it."""
    eps = sizes["rms_norm_eps"]
    emb = embed(w, ids, prefix)
    return jnp.concatenate(
        [_rms_norm(h_prev, w[p + "hnorm.weight"], eps),
         _rms_norm(emb, w[p + "enorm.weight"], eps)],
        axis=-1) @ w[p + "eh_proj.weight"]


def head(w, h, sizes, norm, prefix=""):
    """[n, s, C] -> the logits through the norm named ``norm``."""
    return _rms_norm(h, w[prefix + norm], sizes["rms_norm_eps"]) @ w[
        prefix + "lm_head.weight"]


def shift_left(ids):
    return jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)


def _forward(w, input_ids, sizes, prefix):
    X = expand(embed(w, input_ids, prefix), sizes)
    balance, margins, dropped, landed = 0.0, [], 0.0, []
    for i in range(sizes["num_hidden_layers"]):
        X, b, m, d, n_here = block(w, X, sizes, f"{prefix}layers.{i}.",
                                   i < sizes["first_k_dense_replace"])
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed += [] if n_here is None else [n_here]
    h = reduce(X)
    logits = head(w, h, sizes, "norm.weight", prefix)
    mtp_logits, ids = [], input_ids
    for j in range(sizes.get("num_nextn_predict_layers", 0)):
        p = f"{prefix}mtp.{j}."
        ids = shift_left(ids)
        h, b, m, d, n_here = mtp_block(
            w, mtp_input(w, h, ids, sizes, p, prefix), sizes, p + "block.")
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed.append(n_here)
        mtp_logits.append(head(w, h, sizes, f"mtp.{j}.norm.weight", prefix))
    return (logits, mtp_logits, balance,
            jnp.min(jnp.stack(margins), axis=0), dropped, landed)


def _precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


def _f32(w):
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def forward(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s] token ids -> the main logits [n, s, vocab]."""
    with _precision(precision):
        return _forward(_f32(w), input_ids, sizes, prefix)[0]


def _shifted_ce(logits, input_ids, shift):
    logp = jax.nn.log_softmax(logits[:, :-shift], axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, input_ids[:, shift:, None], axis=-1))


def losses(logits, mtp_logits, input_ids, sizes, balance=0.0):
    """(total, main cross-entropy, MTP cross-entropy): position i's main
    label is token i + 1, module d's is token i + d + 2; a row's last
    positions predict nothing."""
    main = _shifted_ce(logits, input_ids, 1)
    mtp = sum(_shifted_ce(lg, input_ids, d + 2)
              for d, lg in enumerate(mtp_logits)) / max(len(mtp_logits), 1)
    total = (main + sizes["mtp_loss_weight"] * mtp
             + sizes["balance_loss_weight"] * balance)
    return total, main, mtp


def outputs(w, input_ids, sizes, prefix="", precision="highest"):
    """One pass: (main logits, [MTP logits], total loss, main cross-entropy,
    MTP cross-entropy, balancing term, pairs dropped, router margins, pairs
    that landed on the held experts in each expert block)."""
    with _precision(precision):
        logits, mtp_logits, balance, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        total, main, mtp = losses(logits, mtp_logits, input_ids, sizes,
                                  balance)
        return (logits, mtp_logits, total, main, mtp, balance, dropped,
                margin, landed)


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, main cross-entropy, MTP cross-entropy, balancing term, pairs
    dropped)."""
    return outputs(w, input_ids, sizes, prefix, precision)[2:7]
