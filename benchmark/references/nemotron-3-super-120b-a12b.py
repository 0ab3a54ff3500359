"""Plain float32 forward and loss of NVIDIA-Nemotron-3-Super-120B-A12B (HF
``nemotron_h``; Mamba-2: Dao & Gu, arXiv:2405.21060), in straightforward
``jax.numpy`` with no framework, kernel, chunk, sort or cache: the
convolution is four explicit shifted multiply-adds plus the bias, the
state-space scan THE RECURRENCE OVER TOKENS (a ``lax.scan`` that carries
[heads, d_state, d_head]), attention a softmax over every key under an
explicit [queries, keys] mask in blocks of queries, and every held expert
runs on every token under a [tokens, experts] weight matrix that is zero
outside a token's top-k. Weights (and the routers' bias buffers) come as a
dict under the framework's names; Linear weights are [in, out], the held
experts' are stacked [held, in, out], the convolution's taps [4, channels].

rms(x; w) = x rsqrt(mean x^2 + eps) w                 (w from 1, eps 1e-5)
h0 = E[ids].  A layer is ONE sublayer:  h' = h + Mixer_c(rms(h; norm)),
c the layer's kind (``layer_types``: the run layers of the published
``hybrid_override_pattern``, M -> mamba, E -> moe, * -> attention).

``mamba`` (H heads of P on a state of N, G groups; H, G are what is HELD
here — ``mamba_n_heads``, ``mamba_n_groups`` — and the weights are the
held heads' columns and rows):
  [z | xBC | dt] = x W_in   (hidden -> H P + (H P + 2 G N) + H, in that order)
  xBC'_t = silu(b + sum_j w_j xBC_{t - 3 + j})  — causal, one filter and one
  bias a channel, ZERO history before a row's first token
  [x | B | C] = xBC'   (H P + G N + G N; head h reads group h // (H / G))
  dt_t = softplus(dt_t + dt_bias)  (not clamped),  A = -exp(A_log)
  S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T,  S before a row's start = 0
  (the decay through ``exp_nonpositive``, an exp of the reference's own)
  y_t = S_t^T C_t + D x_t
  y' = w * rms_{H P / G}(y * silu(z))  — THE GATE FIRST, one mean square A
  GROUP (its H P / G features);  out = y' W_out  (a partial sum over the
  heads held, where they are a share)

``attention`` (H_q query heads of d on H_kv key/value heads, both the HELD
counts): q = x W_q, k = x W_k, v = x W_v, NOTHING ROTATED, no norm a head;
  o = softmax(q k^T / sqrt(d) + causal mask) v with query head h on
  key/value head h // (H_q / H_kv);  out = concat(o) W_o  (a partial sum)

``moe`` (LatentMoE): s = sigmoid(x W_r) over ALL ``router_experts``; the
  choice is the top-k of s + b (b: ``e_score_correction_bias``, no
  gradient), g = s[chosen] / (sum + 1e-20) x ``routed_scaling_factor``;
  u = x W_dn (hidden -> ``moe_latent_size``);
  r = sum over the chosen experts THAT ARE HELD HERE (``held_experts`` =
  [first, count]) of g_e W2_e relu(W1_e u)^2   (latent -> width -> latent);
  out = r W_up (latent -> hidden) + W2_s relu(W1_s x)^2  (the shared expert
  on the hidden-wide stream). What the absent experts would have added is
  left out, as in the program; with every expert held this is the whole
  layer. No auxiliary loss term.

logits = rms(h; norm) W_head   (untied).
MTP module (one): x = W_eh [rms_h(h_last) ; rms_e(Emb(t shifted left))] ->
an ``attention`` layer, a ``moe`` layer (``mtp_hybrid_override_pattern``
``*E``) -> rms -> the same head; h_last is the last layer's output before
the final norm. A row's last positions see its last token again and carry no
label.
Loss = CE(main_i, t_{i+1}) + ``mtp_loss_weight`` x CE(mtp_i, t_{i+2}).

The held share's row bound: the program computes at most ``held_rows(N)``
(token, choice) pairs a layer (``held_rows_factor`` over the mean N k count
/ E, rounded up to 512); pairs are taken in the order (expert, token,
choice) and the rest dropped. The reference applies the same rule and
reports how many were dropped.

A token's ROUTER MARGIN in an expert layer is about the HELD experts alone:
how far the nearest held expert's biased score is from changing sides — a
chosen one's score over the (k + 1)-th largest, an unchosen one's under the
k-th. A swap among absent experts moves nothing here but the
renormalising sum, continuously (by the two scores' difference over the sum
of 22).

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


def layer_types(sizes):
    """The kinds of the layers run."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def mtp_layer_types(sizes):
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    return [kinds[c] for c in sizes["mtp_hybrid_override_pattern"]]


def shifted(g, by):
    """g [n, s, c] moved ``by`` tokens later in its own row, zeros in
    front: position t holds g_{t - by}."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:, :by]), g[:, :-by]], axis=1)


def exp_nonpositive(x):
    """exp(x) for x <= 0 to float32 rounding, in plain arithmetic: x = k ln 2
    + r (Cody-Waite, ln 2 in two parts), a degree-7 Taylor polynomial on
    |r| <= 0.347 (remainder 5e-9), times 2^k built from its bits. The
    platform's own exp is not used for the decay: on the TPU v5e it is good
    to 5e-6, and the recurrence multiplies a slow head's state by it
    thousands of times over (PERF.md section 6, PR 47)."""
    x = jnp.maximum(x, -87.0)
    k = jnp.round(x * 1.4426950408889634)
    r = (x - k * 0.693359375) - k * -2.12194440e-4
    p = 1 / 5040.0
    for c in (1 / 720.0, 1 / 120.0, 1 / 24.0, 1 / 6.0, 0.5, 1.0, 1.0):
        p = p * r + c
    return p * jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)


def mamba(w, a, sizes, p):
    """The mamba mixer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    heads, d_head = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, d_state = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    inner, state = heads * d_head, groups * d_state
    zxbcdt = a @ w[p + "in_proj.weight"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * state],
                  zxbcdt[..., 2 * inner + 2 * state:])
    taps = w[p + "conv1d.weight"]               # [4, channels]; tap 3 meets t
    k = taps.shape[0]
    xbc = jax.nn.silu(sum(taps[k - 1 - by] * shifted(xbc, by)
                          for by in range(k)) + w[p + "conv1d.bias"])
    x = xbc[..., :inner].reshape(n, s, heads, d_head)
    per = heads // groups                       # head h reads group h // per
    b = jnp.repeat(xbc[..., inner:inner + state].reshape(
        n, s, groups, d_state), per, axis=2)
    c = jnp.repeat(xbc[..., inner + state:].reshape(
        n, s, groups, d_state), per, axis=2)
    dt = jax.nn.softplus(dt + w[p + "dt_bias"])               # [n, s, H]
    rate = -jnp.exp(w[p + "A_log"])                           # [H]

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs                # [n, H, P], [n, H, N] x 2, [n, H]
        state = (exp_nonpositive(dt_t * rate)[..., None, None] * state
                 + b_t[..., :, None] * (dt_t[..., None] * x_t)[..., None, :])
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t)

    zero = jnp.zeros((n, heads, d_state, d_head), jnp.float32)
    _, y = jax.lax.scan(token, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + w[p + "D"][:, None] * x
    gated = y.reshape(n, s, inner) * jax.nn.silu(z)           # the gate FIRST
    normed = _rms(gated.reshape(n, s, groups, inner // groups), 1.0,
                  sizes["layer_norm_epsilon"]).reshape(n, s, inner)
    return (normed * w[p + "norm.weight"]) @ w[p + "out_proj.weight"]


def attention(w, a, sizes, p):
    """The attention mixer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, group = sizes["head_dim"], heads // kv
    q = (a @ w[p + "q_proj.weight"]).reshape(n, s, heads, d).transpose(
        0, 2, 1, 3)
    k = (a @ w[p + "k_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    v = (a @ w[p + "v_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    q = q.reshape(n, kv, group, s, d)      # query head h on kv head h // group

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = jnp.einsum("bkgqd,bksd->bkgqs", q_rows, k) / math.sqrt(d)
        i = first + jnp.arange(q_rows.shape[3])
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # in blocks of queries, so that the [heads, block, s] scores fit; each
    # block meets every key under its mask
    qb = sizes.get("reference_q_block", 256)
    if s > qb and s % qb == 0:
        blocks = q.reshape(n, kv, group, s // qb, qb, d)
        o = jax.lax.map(lambda i: rows(blocks[:, :, :, i], i * qb),
                        jnp.arange(s // qb))
        o = jnp.moveaxis(o, 0, 3).reshape(n, heads, s, d)
    else:
        o = rows(q, 0).reshape(n, heads, s, d)
    o = o.transpose(0, 2, 1, 3).reshape(n, s, heads * d)
    return o @ w[p + "o_proj.weight"]


def held_rows(tokens, sizes):
    first, count = sizes["held_experts"]
    mean = tokens * sizes["num_experts_per_tok"] * count / sizes[
        "router_experts"]
    rows = -(-math.ceil(sizes["held_rows_factor"] * mean) // 512) * 512
    return min(rows, tokens * sizes["num_experts_per_tok"])


def _relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def experts(w, m, sizes, p):
    """The LatentMoE sublayer on normed tokens m [N, hidden]: (output, each
    token's router margin, pairs dropped, pairs that landed on the held
    experts)."""
    tokens = m.shape[0]
    top_k, total = sizes["num_experts_per_tok"], sizes["router_experts"]
    first, count = sizes["held_experts"]
    s = jax.nn.sigmoid(m @ w[p + "gate.weight"])              # [N, E]
    biased = s + w[p + "e_score_correction_bias"]
    ranked, idx = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k + 1)
    idx = idx[:, :top_k]
    chosen = jnp.sum(jax.nn.one_hot(idx, total, dtype=s.dtype), axis=1)
    # the margin of the held experts: a chosen one's over the (k + 1)-th
    # largest score, an unchosen one's under the k-th
    here_biased = biased[:, first:first + count]
    here = chosen[:, first:first + count]                     # [N, held]
    margin = jnp.min(jnp.where(here > 0, here_biased - ranked[:, top_k:],
                               ranked[:, top_k - 1:top_k] - here_biased),
                     axis=-1)
    weights = s * chosen
    if sizes.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    weights = weights * sizes["routed_scaling_factor"]
    # the row bound: held pairs in (expert, token, choice) order; a pair
    # whose rank reaches ``held_rows`` is dropped
    per_expert = jnp.sum(here, axis=0)
    rank = (jnp.cumsum(per_expert) - per_expert)[None, :] + (
        jnp.cumsum(here, axis=0) - here)
    kept = here * (rank < held_rows(tokens, sizes))
    dropped = jnp.sum(here) - jnp.sum(kept)
    held_weights = weights[:, first:first + count] * kept

    u = m @ w[p + "latent_down.weight"]                       # [N, latent]

    def one(acc, xs):
        w_up, w_down, weight = xs
        return acc + weight[:, None] * _relu2(u, w_up, w_down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        w[p + "w_up"], w[p + "w_down"], held_weights.T))
    out = routed @ w[p + "latent_up.weight"] + _relu2(
        m, w[p + "shared.up_proj.weight"], w[p + "shared.down_proj.weight"])
    return out, margin, dropped, jnp.sum(here)


def layer(w, h, sizes, p, kind):
    """One layer on h [n, s, hidden] under the parameter prefix p: (output,
    router margins [n, s] — inf where there is no router —, pairs dropped,
    pairs that landed here)."""
    n, s, hidden = h.shape
    a = _rms(h, w[p + "norm.weight"], sizes["layer_norm_epsilon"])
    none = (jnp.full((n, s), jnp.inf), jnp.zeros(()), jnp.zeros(()))
    if kind == "mamba":
        return (h + mamba(w, a, sizes, p + "mixer."),) + none
    if kind == "attention":
        return (h + attention(w, a, sizes, p + "mixer."),) + none
    y, margin, dropped, landed = experts(w, a.reshape(n * s, hidden), sizes,
                                         p + "mixer.")
    return (h + y.reshape(n, s, hidden), margin.reshape(n, s), dropped,
            landed)


def embed(w, input_ids, prefix=""):
    return w[prefix + "embed_tokens.weight"][input_ids]


def shift_left(ids):
    return jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)


def mtp_input(w, h_last, ids, sizes, p, prefix=""):
    """What MTP module p feeds its layers: h_last is the state it reads, ids
    the tokens ALREADY shifted for it."""
    eps = sizes["layer_norm_epsilon"]
    return jnp.concatenate(
        [_rms(h_last, w[p + "hnorm.weight"], eps),
         _rms(embed(w, ids, prefix), w[p + "enorm.weight"], eps)],
        axis=-1) @ w[p + "eh_proj.weight"]


def head(w, h, sizes, norm, prefix=""):
    """The norm named ``norm`` and the untied head: the logits."""
    return _rms(h, w[norm], sizes["layer_norm_epsilon"]) @ w[
        prefix + "lm_head.weight"]


def shifted_ce(logits, input_ids, shift):
    """Position i's label is token i + ``shift``; a row's last positions
    predict nothing."""
    logp = jax.nn.log_softmax(logits[:, :-shift], axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, input_ids[:, shift:, None], axis=-1))


def losses(logits, mtp_logits, input_ids, sizes):
    """(total, main cross-entropy, MTP cross-entropy; the last 0 without a
    module)."""
    main = shifted_ce(logits, input_ids, 1)
    mtp = sum(shifted_ce(lg, input_ids, d + 2)
              for d, lg in enumerate(mtp_logits)) / max(len(mtp_logits), 1)
    return main + sizes["mtp_loss_weight"] * mtp, main, mtp


def _forward(w, input_ids, sizes, prefix):
    h = embed(w, input_ids, prefix)
    margins, dropped, landed = [], 0.0, []
    for i, kind in enumerate(layer_types(sizes)):
        h, m, d, n_here = layer(w, h, sizes, f"{prefix}layers.{i}.", kind)
        margins.append(m)
        dropped = dropped + d
        landed += [n_here] if kind == "moe" else []
    logits = head(w, h, sizes, prefix + "norm.weight", prefix)
    mtp_logits, ids = [], input_ids
    for j in range(sizes.get("num_nextn_predict_layers", 0)):
        p = f"{prefix}mtp.{j}."
        ids = shift_left(ids)
        h = mtp_input(w, h, ids, sizes, p, prefix)
        for i, kind in enumerate(mtp_layer_types(sizes)):
            h, m, d, n_here = layer(w, h, sizes, f"{p}block.{i}.", kind)
            margins.append(m)
            dropped = dropped + d
            landed += [n_here] if kind == "moe" else []
        mtp_logits.append(head(w, h, sizes, p + "norm.weight", prefix))
    return (logits, mtp_logits, jnp.min(jnp.stack(margins), axis=0), dropped,
            landed)


def _precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


def _f32(w):
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def forward(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s] token ids -> the main logits [n, s, vocab]."""
    with _precision(precision):
        return _forward(_f32(w), input_ids, sizes, prefix)[0]


def outputs(w, input_ids, sizes, prefix="", precision="highest"):
    """One pass: (main logits, [MTP logits], total loss, main cross-entropy,
    MTP cross-entropy, pairs dropped, router margins, pairs that landed on
    the held experts in each expert layer)."""
    with _precision(precision):
        logits, mtp_logits, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        total, main, mtp = losses(logits, mtp_logits, input_ids, sizes)
        return logits, mtp_logits, total, main, mtp, dropped, margin, landed


def loss(w, input_ids, sizes, prefix="", precision="highest"):
    return outputs(w, input_ids, sizes, prefix, precision)[2]
