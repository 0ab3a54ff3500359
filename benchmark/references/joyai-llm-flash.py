"""Plain float32 forward and loss of JoyAI-LLM-Flash (HF ``joyai_llm_flash``;
the layer equations are the DeepSeek-V3 family's, which the config's
``kv_lora_rank``, ``q_lora_rank``, ``topk_method: noaux_tc``,
``n_shared_experts`` and ``num_nextn_predict_layers`` name: DeepSeek-V3
technical report, arXiv:2412.19437, sections 2.1-2.2), in straightforward
``jax.numpy`` with no framework, kernel, sort or cache: every held expert
runs on every token and a [tokens, experts] weight matrix that is zero
outside a token's top-k selects. Weights (and the routers' bias buffers)
come as a dict under the framework's names; Linear weights are [in, out],
the held experts' are stacked [held, in, out].

A block, on h [n, s, hidden]:
  a = rmsnorm(h; input_layernorm)
  c_q = rmsnorm(a W_qa);  q = c_q W_qb -> heads x (nope + rope)
  [c_kv ; k_r] = a W_kva; c_kv = rmsnorm(c_kv); [k_nope ; v] = c_kv W_kvb
  RoPE (pairs (2i, 2i + 1), theta ``rope_theta``) on each head's q_rope and
  on the ONE k_r all heads share; k = [k_nope ; k_r];
  o = softmax(q k^T / sqrt(nope + rope) + causal) v;  h = h + concat(o) W_o
  m = rmsnorm(h; post_attention_layernorm)
  dense (the first ``first_k_dense_replace`` blocks):
      h = h + W_down(silu(m W_gate) * (m W_up))
  expert: s = sigmoid(m W_r) over ALL ``router_experts``; the choice is
      top-k of s + b (b: ``e_score_correction_bias``, no gradient), the
      weights are s at the chosen experts / their sum (``norm_topk_prob``)
      x ``routed_scaling_factor``;
      h = h + shared(m) + sum over the chosen experts THAT ARE HELD HERE
      (``held_experts`` = [first, count]) of w_e expert_e(m).
      What the absent experts would have added is left out, as in the
      program; with every expert held this is the whole layer.
logits = rmsnorm(h; norm) W_head.

MTP module d (one here): x = W_eh [rmsnorm_h(h_prev) ; rmsnorm_e(Emb(t
shifted left by d + 1))] -> one expert block -> rmsnorm -> the same head;
h_prev is the last block's output before the final norm (module 0) or the
module before. A row's last positions see its last token again and carry
no label.

Loss = CE(main_i, t_{i+1}) + ``mtp_loss_weight`` x mean_d CE(mtp_d_i,
t_{i+d+2}) + ``balance_loss_weight`` x sum over expert layers of
E sum_e (n_e / N) mean_t s'[t, e] (s' = s / sum_e s; n_e: assignments to e;
DeepSeek-V3's sequence-wise term times k, over the step's tokens).

The held share's row bound: the program computes at most
``held_rows(N)`` (token, choice) pairs a layer (``held_rows_factor`` over
the mean N k count / E, rounded up to 512); pairs are taken in the order
(expert, token, choice) and the rest dropped. The reference applies the
same rule and reports how many were dropped (0 at every size checked).

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


def _rope(x, theta):
    """x: [..., s, d]; interleaved pairing (2i, 2i + 1)."""
    d, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


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
    """The latent-attention sublayer on normed input a [n, s, hidden]."""
    n, s, _ = a.shape
    heads = sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    c_q = _rms_norm(a @ w[p + "q_a_proj.weight"],
                    w[p + "q_a_layernorm.weight"], eps)
    q = (c_q @ w[p + "q_b_proj.weight"]).reshape(
        n, s, heads, nope + rope).transpose(0, 2, 1, 3)
    kv_a = a @ w[p + "kv_a_proj_with_mqa.weight"]
    c_kv = _rms_norm(kv_a[..., :rank], w[p + "kv_a_layernorm.weight"], eps)
    k_r = _rope(kv_a[..., rank:], theta)                      # [n, s, rope]
    kv = (c_kv @ w[p + "kv_b_proj.weight"]).reshape(
        n, s, heads, nope + dv).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
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
    # (8,192 positions: 8.6 GB); each block sees every key
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


def block(w, h, sizes, p, dense):
    """One decoder block on h [n, s, hidden] under the parameter prefix p:
    (output, balancing term, router margins [n, s], pairs dropped, pairs
    that landed here)."""
    n, s, hidden = h.shape
    eps = sizes["rms_norm_eps"]
    h = h + attention(w, _rms_norm(h, w[p + "input_layernorm.weight"], eps),
                      sizes, p + "self_attn.")
    m = _rms_norm(h, w[p + "post_attention_layernorm.weight"], eps)
    if dense:
        return (h + _swiglu(m, w, p + "mlp."), 0.0,
                jnp.full((n, s), jnp.inf), 0.0, None)
    y, balance, margin, dropped, landed = experts(
        w, m.reshape(n * s, hidden), sizes, p + "mlp.")
    return (h + y.reshape(n, s, hidden), balance, margin.reshape(n, s),
            dropped, landed)


def mtp_input(w, h_prev, ids, sizes, p, prefix=""):
    """What MTP module p feeds its block: h_prev is the state it reads,
    ids the tokens ALREADY shifted for it."""
    eps = sizes["rms_norm_eps"]
    emb = w[prefix + "embed_tokens.weight"][ids]
    return jnp.concatenate(
        [_rms_norm(h_prev, w[p + "hnorm.weight"], eps),
         _rms_norm(emb, w[p + "enorm.weight"], eps)],
        axis=-1) @ w[p + "eh_proj.weight"]


def shift_left(ids):
    return jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)


def _forward(w, input_ids, sizes, prefix):
    eps = sizes["rms_norm_eps"]
    h = w[prefix + "embed_tokens.weight"][input_ids]
    balance, margins, dropped, landed = 0.0, [], 0.0, []
    for i in range(sizes["num_hidden_layers"]):
        h, b, m, d, n_here = block(w, h, sizes, f"{prefix}layers.{i}.",
                                   i < sizes["first_k_dense_replace"])
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed += [] if n_here is None else [n_here]
    head = w[prefix + "lm_head.weight"]
    logits = _rms_norm(h, w[prefix + "norm.weight"], eps) @ head
    mtp_logits, ids = [], input_ids
    for j in range(sizes.get("num_nextn_predict_layers", 0)):
        p = f"{prefix}mtp.{j}."
        ids = shift_left(ids)
        h, b, m, d, n_here = block(
            w, mtp_input(w, h, ids, sizes, p, prefix), sizes, p + "block.",
            False)
        balance, dropped = balance + b, dropped + d
        margins.append(m)
        landed.append(n_here)
        mtp_logits.append(_rms_norm(h, w[p + "norm.weight"], eps) @ head)
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


def outputs(w, input_ids, sizes, prefix="", precision="highest"):
    """One pass: (main logits, [MTP logits], total loss, main cross-entropy,
    MTP cross-entropy, balancing term, pairs dropped, router margins, pairs
    that landed on the held experts in each expert block).
    Position i's main label is token i + 1, module d's is token i + d + 2;
    a row's last positions predict nothing."""
    with _precision(precision):
        logits, mtp_logits, balance, margin, dropped, landed = _forward(
            _f32(w), input_ids, sizes, prefix)
        main = _shifted_ce(logits, input_ids, 1)
        mtp = sum(_shifted_ce(lg, input_ids, d + 2)
                  for d, lg in enumerate(mtp_logits)) / max(
                      len(mtp_logits), 1)
        total = (main + sizes["mtp_loss_weight"] * mtp
                 + sizes["balance_loss_weight"] * balance)
        return (logits, mtp_logits, total, main, mtp, balance, dropped,
                margin, landed)


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, main cross-entropy, MTP cross-entropy, balancing term, pairs
    dropped)."""
    return outputs(w, input_ids, sizes, prefix, precision)[2:7]


def router_margin(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s]: by how much a token's k-th biased router score exceeds its
    (k + 1)-th, the smallest over the expert layers (the MTP block's
    among them)."""
    with _precision(precision):
        return _forward(_f32(w), input_ids, sizes, prefix)[3]
