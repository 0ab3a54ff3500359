"""Plain float32 forward and loss of Qwen3-Next-80B-A3B (HF ``qwen3_next``;
Gated DeltaNet: Yang et al., arXiv:2412.06464), in straightforward
``jax.numpy`` with no framework, kernel, chunk, sort or cache: Gated
DeltaNet is the recurrence over tokens in a ``lax.scan``, its convolution
four shifted multiply-adds, attention a softmax over every key in blocks of
queries, every held expert runs on every token and a [tokens, experts]
weight matrix that is zero outside a token's top-k selects. Weights come as
a dict under the framework's names; Linear weights are [in, out], the
convolution's [taps, channels], the held experts' are stacked
[held, in, out].

zrms(x; w) = x rsqrt(mean x^2 + eps) (1 + w)      (zero-centred, w from 0)
A block, on h [n, s, hidden] (pre-norm):
  h = h + Mixer(zrms(h; input_layernorm))
  h = h + Experts(zrms(h; post_attention_layernorm))
Layer i (from 0) is full attention where (i + 1) % full_attention_interval
== 0, else Gated DeltaNet.

Gated DeltaNet (H_k key heads serve H_v value heads, r = H_v / H_k each;
d_k, d_v):
  qkvz = a W_qkvz, ba = a W_ba, both laid out a KEY head at a time:
      [q d_k | k d_k | v r d_v | z r d_v],  [b r | a r]
  x = silu(conv([q | k | v] over all heads)), every channel its own taps over
  positions t - 3 .. t (tap 3 meets t), zero history, no bias;
  q = l2norm(q) d_k^-0.5, k = l2norm(k) a key head (eps 1e-6), each then
  serving its r neighbouring value heads;
  beta_t = sigmoid(b), g_t = -exp(A_log) softplus(a + dt_bias)  [H_v], <= 0
  per value head, S_0 = 0 [d_k, d_v]:  S' = exp(g_t) S_{t-1};
      u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  o_t = S_t^T q_t
  y = (w * rmsnorm_{d_v}(o) * silu(z)) W_out         (w from 1, NOT zrms)

Gated attention (H query heads on H_kv key/value heads of d):
  q' = a W_q laid out a head at a time [query d | gate d]; k = a W_k,
  v = a W_v; query and k through zrms over a head's d features (q_norm,
  k_norm); rotate-half RoPE (theta ``rope_theta``) on features 0 ..
  d x partial_rotary_factor - 1 only; o = softmax(q k^T d^-0.5 + causal) v
  with query head h on key/value head h // (H / H_kv);
  y = (concat(o) * sigmoid(gate)) W_o

Experts: p = softmax(m W_r) over ALL ``router_experts``; the choice is
  top-k of p, the weights p at the chosen experts / their sum;
  out = sigmoid(m w_s) * shared(m) + sum over the chosen experts THAT ARE
  HELD HERE (``held_experts`` = [first, count]) of w_e expert_e(m). What the
  absent experts would have added is left out, as in the program; with
  every expert held this is the whole layer.
logits = zrms(h; norm) W_head.

Loss = CE(logits_i, t_{i+1}) + ``router_aux_loss_coef`` x sum over layers
of E sum_e (n_e / N) mean_t p[t, e] (n_e: assignments to e), over the
step's tokens. No MTP module (the catalog row's config holds no MTP key and
HF's model drops those weights).

The held share's row bound is the program's: at most ``held_rows(N)``
(token, choice) pairs a layer, taken in (expert, token, choice) order, the
rest dropped and counted (0 at every size checked).

The recurrence takes its decay through ``exp_nonpositive`` (the Kimi-Linear
reference's own exp, for its reason: PERF.md section 6, PR 32).

Every caller runs this under ``jax.default_matmul_precision("highest")``
(the entry points set it; ``precision=None`` leaves the platform's default,
which is how the check shows that a lower precision fails its tolerance).
"""
import contextlib
import math

import jax
import jax.numpy as jnp


def _zrms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _swiglu(m, w, p):
    return (jax.nn.silu(m @ w[p + "gate_proj.weight"])
            * (m @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


def layer_types(sizes):
    every = sizes["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(sizes["num_hidden_layers"])]


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
    |r| <= 0.347 (remainder 5e-9), times 2^k built from its bits (the
    Kimi-Linear reference's, for its reason: the TPU's float32 exp is good
    to 5e-6, which a recurrence compounds over a slow head's memory)."""
    x = jnp.maximum(x, -87.0)
    k = jnp.round(x * 1.4426950408889634)
    r = (x - k * 0.693359375) - k * -2.12194440e-4
    p = 1 / 5040.0
    for c in (1 / 720.0, 1 / 120.0, 1 / 24.0, 1 / 6.0, 0.5, 1.0, 1.0):
        p = p * r + c
    return p * jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token with ONE decay a head: q, k [n, s, H,
    d_k], v [n, s, H, d_v], g and beta [n, s, H] -> (o [n, s, H, d_v], the
    final state [n, H, d_k, d_v])."""
    n, _, heads, d = k.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = exp_nonpositive(g_t)[..., None, None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", state,
                                                  k_t))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, q_t)

    state, o = jax.lax.scan(
        step, jnp.zeros((n, heads, d, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def delta_net(w, a, sizes, p):
    """The Gated DeltaNet sublayer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    r = hv // hk
    qkvz = (a @ w[p + "in_proj_qkvz.weight"]).reshape(
        n, s, hk, 2 * dk + 2 * r * dv)
    ba = (a @ w[p + "in_proj_ba.weight"]).reshape(n, s, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, s, hv, dv)
    b, a_in = ba[..., :r].reshape(n, s, hv), ba[..., r:].reshape(n, s, hv)
    mixed = short_conv(jnp.concatenate(
        [q.reshape(n, s, hk * dk), k.reshape(n, s, hk * dk),
         v.reshape(n, s, hv * dv)], axis=-1), w[p + "conv1d.weight"])
    q = mixed[..., :hk * dk].reshape(n, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(n, s, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(n, s, hv, dv)

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + sizes.get("gdn_l2_eps", 1e-6))

    q = jnp.repeat(l2norm(q) * dk ** -0.5, r, axis=2)
    k = jnp.repeat(l2norm(k), r, axis=2)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w[p + "A_log"]) * jax.nn.softplus(a_in + w[p + "dt_bias"])
    o, _ = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + sizes["rms_norm_eps"]) * w[p + "norm.weight"]
    return (o * jax.nn.silu(z)).reshape(n, s, hv * dv) @ w[
        p + "out_proj.weight"]


def rope(x, theta, rotary):
    """Rotate-half RoPE on the first ``rotary`` features of x [n, h, s, d]."""
    s = x.shape[2]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                           / rotary))
    angles = jnp.outer(jnp.arange(s), inv)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def attention(w, a, sizes, p):
    """The gated grouped-query attention sublayer on normed input a [n, s,
    hidden]."""
    n, s, _ = a.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    rotary = int(d * sizes["partial_rotary_factor"])
    group = heads // kv
    qg = (a @ w[p + "q_proj.weight"]).reshape(n, s, heads, 2 * d)
    gate = qg[..., d:].reshape(n, s, heads * d)
    q = _zrms(qg[..., :d], w[p + "q_norm.weight"], eps).transpose(0, 2, 1, 3)
    k = _zrms((a @ w[p + "k_proj.weight"]).reshape(n, s, kv, d),
              w[p + "k_norm.weight"], eps).transpose(0, 2, 1, 3)
    v = (a @ w[p + "v_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    q = rope(q, sizes["rope_theta"], rotary).reshape(n, kv, group, s, d)
    k = rope(k, sizes["rope_theta"], rotary)

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = jnp.einsum("bkgqd,bksd->bkgqs", q_rows, k) * d ** -0.5
        at = first + jnp.arange(q_rows.shape[3])
        causal = at[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # in blocks of queries where the [heads, s, s] scores would not fit
    # (16,384 positions: 17 GB); each block sees every key
    qb = sizes.get("reference_q_block", 512)
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
    """The expert sublayer on normed tokens m [N, hidden]: (output, the
    balancing term, each token's router margin — the gap between its k-th
    and (k + 1)-th router LOGIT —, pairs dropped, pairs that landed on the
    held experts)."""
    tokens = m.shape[0]
    top_k, total = sizes["num_experts_per_tok"], sizes["router_experts"]
    first, count = sizes["held_experts"]
    logits = m @ w[p + "gate.weight"]                         # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    ranked, idx = jax.lax.top_k(jax.lax.stop_gradient(logits), top_k + 1)
    idx = idx[:, :top_k]
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = jnp.sum(jax.nn.one_hot(idx, total, dtype=probs.dtype), axis=1)
    weights = probs * chosen
    if sizes.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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
    out = routed + jax.nn.sigmoid(m @ w[p + "shared_gate.weight"]) * _swiglu(
        m, w, p + "shared.")
    balance = total * jnp.sum(jnp.sum(chosen, axis=0) / tokens
                              * jnp.mean(probs, axis=0))
    return out, balance, margin, dropped, jnp.sum(here)


def block(w, h, sizes, p, dense, mixer):
    """One decoder block on h [n, s, hidden] under the parameter prefix p:
    (output, balancing term, router margins [n, s], pairs dropped, pairs
    that landed here). ``dense`` is always False here (every layer has the
    expert layer); the argument keeps the other references' signature."""
    del dense
    n, s, hidden = h.shape
    eps = sizes["rms_norm_eps"]
    a = _zrms(h, w[p + "input_layernorm.weight"], eps)
    if mixer == "linear_attention":
        h = h + delta_net(w, a, sizes, p + "linear_attn.")
    else:
        h = h + attention(w, a, sizes, p + "self_attn.")
    m = _zrms(h, w[p + "post_attention_layernorm.weight"], eps)
    y, balance, margin, dropped, landed = experts(
        w, m.reshape(n * s, hidden), sizes, p + "mlp.")
    return (h + y.reshape(n, s, hidden), balance, margin.reshape(n, s),
            dropped, landed)


def _forward(w, input_ids, sizes, prefix):
    h = w[prefix + "embed_tokens.weight"][input_ids]
    balance, margins, dropped, landed = 0.0, [], 0.0, []
    for i, mixer in enumerate(layer_types(sizes)):
        h, b, m, d, n_here = block(w, h, sizes, f"{prefix}layers.{i}.",
                                   False, mixer)
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed.append(n_here)
    logits = _zrms(h, w[prefix + "norm.weight"],
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
    block). Position i's label is token i + 1; a row's last position
    predicts nothing."""
    with _precision(precision):
        logits, balance, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1:, None], axis=-1))
        total = ce + sizes["router_aux_loss_coef"] * balance
        return logits, total, ce, balance, dropped, margin, landed


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, cross-entropy, balancing term, pairs dropped)."""
    return outputs(w, input_ids, sizes, prefix, precision)[1:5]
