"""Plain float32 forward and loss of Kimi-Linear-48B-A3B (HF ``kimi_linear``;
Kimi Linear technical report, arXiv:2510.26692), in straightforward
``jax.numpy`` with no framework, kernel, chunk, sort or cache: Kimi Delta
Attention is the recurrence over tokens in a ``lax.scan``, its convolution
four shifted multiply-adds, every held expert runs on every token and a
[tokens, experts] weight matrix that is zero outside a token's top-k
selects. Weights (and the routers' bias buffers) come as a dict under the
framework's names; Linear weights are [in, out], a convolution's
[taps, channels], the held experts' are stacked [held, in, out].

A block, on h [n, s, hidden] (pre-norm, ``rms_norm_eps``):
  h = h + Mixer(rmsnorm(h; input_layernorm))
  h = h + FFN(rmsnorm(h; post_attention_layernorm))
Mixer by layer type (``linear_attn_config``'s two 1-indexed lists):

Kimi Delta Attention (H heads of d; layer in ``kda_layers``):
  q', k', v' = a W_q, a W_k, a W_v;  x'' = silu(conv(x')), every channel
  its own taps over positions t - 3 .. t (tap 3 meets t), zero history;
  q = l2norm(q'') d^-0.5, k = l2norm(k'') per head (eps 1e-6), v = v''
  g_t = -exp(A_log_h) softplus((a W_fa) W_fb + dt_bias)   [H, d], <= 0
  beta_t = sigmoid(a W_b)                                  [H]
  per head, S_0 = 0 [d, d]:  S' = diag(exp(g_t)) S_{t-1};
      u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  o_t = S_t^T q_t
  y = (sigmoid((a W_ga) W_gb) * rmsnorm_d(o; o_norm)) W_o

Latent attention without positions (layer in ``full_attn_layers``):
  q = a W_q -> heads x (nope + rope);  [c ; k_r] = a W_kva;
  [k_nope ; v] = rmsnorm(c) W_kvb;  k = [k_nope ; k_r for every head],
  NOTHING rotated;  o = softmax(q k^T / sqrt(nope + rope) + causal) v;
  y = concat(o) W_o

FFN: dense SwiGLU in the first ``first_k_dense_replace`` blocks; elsewhere
  s = sigmoid(m W_r) over ALL ``router_experts``; the choice is top-k of
  s + b (b: ``e_score_correction_bias``, no gradient), the weights are s at
  the chosen experts / (their sum + 1e-20) x ``routed_scaling_factor``;
  out = shared(m) + sum over the chosen experts THAT ARE HELD HERE
  (``held_experts`` = [first, count]) of w_e expert_e(m). What the absent
  experts would have added is left out, as in the program; with every
  expert held this is the whole layer.
logits = rmsnorm(h; norm) W_head.

Loss = CE(logits_i, t_{i+1}) + ``balance_loss_weight`` x sum over expert
layers of E sum_e (n_e / N) mean_t s'[t, e] (s' = s / sum_e s; n_e:
assignments to e), over the step's tokens.

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


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _swiglu(m, w, p):
    return (jax.nn.silu(m @ w[p + "gate_proj.weight"])
            * (m @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


def layer_types(sizes):
    linear = sizes["linear_attn_config"]
    return ["kda" if i in linear["kda_layers"] else "mla"
            for i in range(1, sizes["num_hidden_layers"] + 1)]


def held_rows(tokens, sizes):
    first, count = sizes["held_experts"]
    mean = tokens * sizes["num_experts_per_tok"] * count / sizes[
        "router_experts"]
    rows = -(-math.ceil(sizes["held_rows_factor"] * mean) // 512) * 512
    return min(rows, tokens * sizes["num_experts_per_tok"])


def short_conv(x, taps):
    """silu of the causal depthwise convolution: x [n, s, c], taps [k, c];
    tap k - 1 meets position t, tap 0 position t - k + 1."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * taps[j] for j in range(k)))


def exp_nonpositive(x):
    """exp(x) for x <= 0 to float32 rounding, in plain arithmetic: x = k ln 2
    + r (Cody-Waite, ln 2 in two parts), a degree-7 Taylor polynomial on
    |r| <= 0.347 (remainder 5e-9), times 2^k built from its bits. The
    platform's own exp is not used for the decay: on the TPU v5e it is good
    to 5e-6 (my chip run, PR 32: against float64), and the recurrence
    multiplies a slow channel's state by it a thousand times over, which
    left the recurrence further from float64 (4.6e-6 at the median token)
    than the chunked form it is the reference for (1.0e-6)."""
    x = jnp.maximum(x, -87.0)
    k = jnp.round(x * 1.4426950408889634)
    r = (x - k * 0.693359375) - k * -2.12194440e-4
    p = 1 / 5040.0
    for c in (1 / 720.0, 1 / 120.0, 1 / 24.0, 1 / 6.0, 0.5, 1.0, 1.0):
        p = p * r + c
    return p * jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token: q, k, g [n, s, H, d], v [n, s, H, dv],
    beta [n, s, H] -> (o [n, s, H, dv], the final state [n, H, d, dv])."""
    n, _, heads, d = k.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = exp_nonpositive(g_t)[..., None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", state,
                                                  k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t)

    state, o = jax.lax.scan(
        step, jnp.zeros((n, heads, d, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def delta_attention(w, a, sizes, p):
    """The Kimi Delta Attention sublayer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    linear = sizes["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]

    def stream(name):
        return short_conv(a @ w[p + name + "_proj.weight"],
                          w[p + name + "_conv.weight"]).reshape(n, s, heads,
                                                                d)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + sizes.get("kda_l2_eps", 1e-6))

    q, k, v = l2norm(stream("q")) * d ** -0.5, l2norm(stream("k")), stream(
        "v")
    z = (a @ w[p + "f_a_proj.weight"]) @ w[p + "f_b_proj.weight"] + w[
        p + "dt_bias"]
    g = -jnp.exp(w[p + "A_log"])[:, None] * jax.nn.softplus(
        z.reshape(n, s, heads, d))
    beta = jax.nn.sigmoid(a @ w[p + "b_proj.weight"])
    o, _ = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((a @ w[p + "g_a_proj.weight"])
                          @ w[p + "g_b_proj.weight"])
    o = _rms_norm(o, w[p + "o_norm.weight"], sizes["rms_norm_eps"])
    return (o.reshape(n, s, heads * d) * gate) @ w[p + "o_proj.weight"]


def attention(w, a, sizes, p):
    """The latent-attention sublayer (no q rank, nothing rotated) on normed
    input a [n, s, hidden]."""
    n, s, _ = a.shape
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    q = (a @ w[p + "q_proj.weight"]).reshape(
        n, s, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = a @ w[p + "kv_a_proj_with_mqa.weight"]
    c_kv = _rms_norm(kv_a[..., :rank], w[p + "kv_a_layernorm.weight"],
                     sizes["rms_norm_eps"])
    k_r = kv_a[..., rank:]                                    # [n, s, rope]
    kv = (c_kv @ w[p + "kv_b_proj.weight"]).reshape(
        n, s, heads, nope + dv).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = (jnp.einsum("bhqd,bhkd->bhqk", q_rows[..., :nope], k_nope)
                  + jnp.einsum("bhqd,bkd->bhqk", q_rows[..., nope:], k_r)
                  ) / math.sqrt(nope + rope)
        at = first + jnp.arange(q_rows.shape[2])
        causal = at[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    # in blocks of queries where the [heads, s, s] scores would not fit
    # (16,384 positions: 34 GB); each block sees every key
    qb = sizes.get("reference_q_block", 512)
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
    if sizes.get("moe_renormalize", True):
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


def block(w, h, sizes, p, dense, mixer):
    """One decoder block on h [n, s, hidden] under the parameter prefix p:
    (output, balancing term, router margins [n, s], pairs dropped, pairs
    that landed here)."""
    n, s, hidden = h.shape
    eps = sizes["rms_norm_eps"]
    mix = delta_attention if mixer == "kda" else attention
    h = h + mix(w, _rms_norm(h, w[p + "input_layernorm.weight"], eps),
                sizes, p + "self_attn.")
    m = _rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
    if dense:
        return (h + _swiglu(m, w, p + "mlp."), 0.0,
                jnp.full((n, s), jnp.inf), 0.0, None)
    y, balance, margin, dropped, landed = experts(
        w, m.reshape(n * s, hidden), sizes, p + "mlp.")
    return (h + y.reshape(n, s, hidden), balance, margin.reshape(n, s),
            dropped, landed)


def _forward(w, input_ids, sizes, prefix):
    h = w[prefix + "embed_tokens.weight"][input_ids]
    balance, margins, dropped, landed = 0.0, [], 0.0, []
    for i, mixer in enumerate(layer_types(sizes)):
        h, b, m, d, n_here = block(w, h, sizes, f"{prefix}layers.{i}.",
                                   i < sizes["first_k_dense_replace"], mixer)
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed += [] if n_here is None else [n_here]
    logits = _rms_norm(h, w[prefix + "norm.weight"],
                       sizes["rms_norm_eps"]) @ w[prefix + "lm_head.weight"]
    return (logits, balance, jnp.min(jnp.stack(margins), axis=0), dropped,
            landed)


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
    """One pass: (logits, total loss, cross-entropy, balancing term, pairs
    dropped, router margins, pairs that landed on the held experts in each
    expert block). Position i's label is token i + 1; a row's last position
    predicts nothing."""
    with _precision(precision):
        logits, balance, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1:, None], axis=-1))
        total = ce + sizes["balance_loss_weight"] * balance
        return logits, total, ce, balance, dropped, margin, landed


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, cross-entropy, balancing term, pairs dropped)."""
    return outputs(w, input_ids, sizes, prefix, precision)[1:5]
