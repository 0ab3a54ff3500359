"""Plain float32 forward and loss of OLMoE (Muennighoff et al. 2024,
arXiv:2409.02060; the layer equations of HF transformers
``models/olmoe/modeling_olmoe.py``), in straightforward ``jax.numpy`` with
no framework, kernel, sort, capacity or cache: every expert runs on every
token and a [tokens, experts] weight matrix that is zero outside a token's
top-k selects. Weights come as a dict under the framework's parameter
names (as a checkpoint would name them); Linear weights are [in, out], the
experts' are stacked [experts, in, out].

A layer: a = rmsnorm(h; input_layernorm); q, k, v = a Wq, a Wk, a Wv (no
bias); q, k = rmsnorm over all ``hidden_size`` features (QK-norm), then
the split into heads; rotate-half RoPE (pairs (i, i + d/2), theta
``rope_theta``); o = softmax(q k^T / sqrt(d) + causal) v; h = h + o Wo.
m = rmsnorm(h; post_attention_layernorm); r = m Wr; p = softmax(r);
(w, idx) = top-k(p), NOT renormalised (``norm_topk_prob`` false);
y = sum_j w_j Wdown[idx_j](silu(m Wgate[idx_j]) * (m Wup[idx_j]));
h = h + y. logits = rmsnorm(h; norm) Whead.

Loss = next-token cross-entropy (mean over the s - 1 predicted positions)
+ ``router_aux_loss_coef`` x sum over layers of L_lb
+ ``router_z_loss_coef`` x sum over layers of L_z, with
L_lb = E sum_e (n_e / N) mean_t p[t, e] (n_e: assignments to e among the
top-k of the layer's N tokens; HF ``load_balancing_loss_func``) and
L_z = mean_t logsumexp(r_t)^2 (ST-MoE; OLMoE's recipe, section 4.1.6).

Departure from the source, the same as the configuration's file lists: HF
computes L_lb once over the concatenation of all layers' router outputs;
here it is computed a layer and summed (with one layer they are equal).

On a TPU a float32 matmul runs in bf16 passes unless the precision is
raised, so every caller runs this under
``jax.default_matmul_precision("highest")`` (``forward`` and ``loss_terms``
set it themselves; ``precision=None`` leaves the platform's default, which
is how the check shows that a lower precision fails its tolerance).
"""
import contextlib

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x: [n, heads, s, d]; rotate-half pairing (i, i + d/2)."""
    d, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def _experts(m, w, lp, gate_weights):
    """Every expert on every token, one after another, summed with the
    [tokens, experts] weights (zero outside a token's top-k)."""
    def one(acc, xs):
        w_gate, w_up, w_down, weight = xs
        y = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        w[lp + "mlp.w_gate"], w[lp + "mlp.w_up"], w[lp + "mlp.w_down"],
        gate_weights.T))
    return out


def _forward(w, input_ids, sizes, prefix):
    n, s = input_ids.shape
    heads = sizes["num_attention_heads"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    top_k, experts = sizes["num_experts_per_tok"], sizes["num_experts"]
    h = w[prefix + "embed_tokens.weight"][input_ids]
    hidden = h.shape[-1]
    d = hidden // heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    balance, z, margin = [], [], []
    for i in range(sizes["num_hidden_layers"]):
        lp = f"{prefix}layers.{i}."

        def split(t):
            return t.reshape(n, s, heads, d).transpose(0, 2, 1, 3)

        a = _rms_norm(h, w[lp + "input_layernorm.weight"], eps)
        q = _rms_norm(a @ w[lp + "self_attn.q_proj.weight"],
                      w[lp + "self_attn.q_norm.weight"], eps)
        k = _rms_norm(a @ w[lp + "self_attn.k_proj.weight"],
                      w[lp + "self_attn.k_norm.weight"], eps)
        v = a @ w[lp + "self_attn.v_proj.weight"]
        q, k, v = _rope(split(q), theta), _rope(split(k), theta), split(v)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        o = o.transpose(0, 2, 1, 3).reshape(n, s, hidden)
        h = h + o @ w[lp + "self_attn.o_proj.weight"]

        m = _rms_norm(h, w[lp + "post_attention_layernorm.weight"],
                      eps).reshape(n * s, hidden)
        r = m @ w[lp + "mlp.gate.weight"]                   # [N, E]
        p = jax.nn.softmax(r, axis=-1)
        ranked, idx = jax.lax.top_k(r, top_k + 1)
        idx = idx[:, :top_k]
        margin.append(ranked[:, top_k - 1] - ranked[:, top_k])
        chosen = jnp.sum(jax.nn.one_hot(idx, experts, dtype=p.dtype),
                         axis=1)                            # [N, E] 0/1
        if sizes.get("norm_topk_prob", False):
            raise NotImplementedError("OLMoE keeps the softmax's weights")
        h = h + _experts(m, w, lp, p * chosen).reshape(n, s, hidden)
        balance.append(experts * jnp.sum(
            jnp.sum(chosen, axis=0) / (n * s) * jnp.mean(p, axis=0)))
        z.append(jnp.mean(jax.nn.logsumexp(r, axis=-1) ** 2))
    logits = (_rms_norm(h, w[prefix + "norm.weight"], eps)
              @ w[prefix + "lm_head.weight"])
    return (logits, sum(balance), sum(z),
            jnp.min(jnp.stack(margin), axis=0).reshape(n, s))


def _precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


def forward(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s] token ids -> logits [n, s, vocab]."""
    with _precision(precision):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        return _forward(w, input_ids, sizes, prefix)[0]


def loss_terms(w, input_ids, sizes, prefix="", precision="highest"):
    """(total, cross-entropy, sum of L_lb, sum of L_z): the label of
    position t is the token at t + 1; the last position predicts nothing."""
    with _precision(precision):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        logits, balance, z, _ = _forward(w, input_ids, sizes, prefix)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(
            logp, input_ids[:, 1:, None], axis=-1))
        total = (ce + sizes["router_aux_loss_coef"] * balance
                 + sizes["router_z_loss_coef"] * z)
        return total, ce, balance, z


def router_margin(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s]: by how much a token's k-th router logit exceeds its
    (k + 1)-th, the smallest over the layers. A token whose margin is under
    the error of the arithmetic it is compared with may rightly take
    another expert there: the comparison leaves such tokens out, and counts
    them."""
    with _precision(precision):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        return _forward(w, input_ids, sizes, prefix)[3]
