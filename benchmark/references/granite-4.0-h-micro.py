"""Plain float32 forward and loss of granite-4.0-h-micro (ibm-granite, HF
``granitemoehybrid``), in straightforward ``jax.numpy`` with no framework,
kernel, chunk or cache: the convolution is four explicit shifted
multiply-adds plus the bias, the state-space scan THE RECURRENCE OVER
TOKENS (a ``lax.scan`` that carries [heads, d_state, d_head]), attention a
softmax over every key under an explicit [queries, keys] mask, in blocks of
queries. Weights come as a dict under the framework's names; Linear weights
are [in, out], the convolution's taps [4, channels] and its bias [channels].

rms(x; w) = x rsqrt(mean x^2 + eps) w                       (w from 1)
h0 = embedding_multiplier x E[ids]
A block, on h [n, s, hidden], with m = residual_multiplier:
  a  = h + m Mixer(rms(h; input_layernorm))
  h' = a + m MLP(rms(a; post_attention_layernorm))
  MLP(x) = W_down(silu(W_gate x) * W_up x)    on EVERY layer (HF's
  ``shared_mlp``: ``input_linear`` = [W_gate | W_up], split in that order)
The layers run are the first ``num_hidden_layers`` of ``layer_types``.

``mamba`` mixer (Mamba-2 / SSD; H heads of P, one state of N x P a head, G
groups):
  [z | xBC | dt] = x W_in   (hidden -> H P + (H P + 2 G N) + H, in that order)
  xBC'_t = silu(b + sum_j w_j xBC_{t - 3 + j})  — causal, one filter and ONE
  BIAS a channel (tap 3 meets the token itself), ZERO history before a row's
  first token: row r never sees row r - 1
  [x | B | C] = xBC'   (H P + G N + G N; head h reads group h // (H / G))
  dt_t = softplus(dt_t + dt_bias), A = -exp(A_log)             (a head)
  S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T,  S before a row's start = 0
  (the decay through ``exp_nonpositive``, an exp of the reference's own)
  y_t = S_t^T C_t + D x_t
  y' = rms_{H P}(y * silu(z); norm)  — THE GATE FIRST, one mean square over
  all H P features;  out = y' W_out

``attention`` mixer (H_q query heads on H_kv key/value heads of d = hidden /
H_q): q = x W_q, k = x W_k, v = x W_v, NOTHING ROTATED, no norm a head;
key j is visible to query i iff j <= i;
  o = softmax(q k^T x attention_multiplier + mask) v with query head h on
  key/value head h // (H_q / H_kv);  out = concat(o) W_o
(``attention_multiplier`` is 1/64 at d = 64, not d ** -0.5.)

logits = rms(h; norm) E^T / logits_scaling                  (the head is tied)
Loss = CE(logits_i, t_{i+1}) over the step's tokens.

Every caller runs this under ``jax.default_matmul_precision("highest")``
(the entry points set it; ``precision=None`` leaves the platform's default,
which is how the check shows that a lower precision fails its tolerance).
"""
import contextlib

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def layer_types(sizes):
    """The types of the layers run: the published list's first
    ``num_hidden_layers``."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def shifted(g, by):
    """g [n, s, c] moved ``by`` tokens later in its own row, zeros in
    front: position t holds g_{t - by}."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:, :by]), g[:, :-by]], axis=1)


def exp_nonpositive(x):
    """exp(x) for x <= 0 to float32 rounding, in plain arithmetic: x = k ln 2
    + r (Cody-Waite, ln 2 in two parts), a degree-7 Taylor polynomial on
    |r| <= 0.347 (remainder 5e-9), times 2^k built from its bits (the
    Kimi-Linear reference's function, for its reason). The platform's own
    exp is not used for the decay: on the TPU v5e it is good to 5e-6, and
    the recurrence multiplies a slow head's state by it thousands of times
    over — with it the recurrence read 3.1e-5 at the worst token and 1.2e-5
    at the median against the program's chunked form (my chip run, PR 47),
    which takes one exponential of a summed decay."""
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
    mixed = sum(taps[k - 1 - by] * shifted(xbc, by) for by in range(k))
    if sizes.get("mamba_conv_bias", True):
        mixed = mixed + w[p + "conv1d.bias"]
    xbc = jax.nn.silu(mixed)
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
    return _rms(gated, w[p + "norm.weight"],
                sizes["rms_norm_eps"]) @ w[p + "out_proj.weight"]


def attention(w, a, sizes, p):
    """The attention mixer on normed input a [n, s, hidden]."""
    n, s, hidden = a.shape
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, group = hidden // heads, heads // kv
    q = (a @ w[p + "q_proj.weight"]).reshape(n, s, heads, d).transpose(
        0, 2, 1, 3)
    k = (a @ w[p + "k_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    v = (a @ w[p + "v_proj.weight"]).reshape(n, s, kv, d).transpose(
        0, 2, 1, 3)
    q = q.reshape(n, kv, group, s, d)      # query head h on kv head h // group

    def rows(q_rows, first):
        """Attention of a block of queries (positions ``first`` on)."""
        scores = jnp.einsum("bkgqd,bksd->bkgqs", q_rows, k) * sizes[
            "attention_multiplier"]
        i = first + jnp.arange(q_rows.shape[3])
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", probs, v)

    # in blocks of queries where the [heads, s, s] scores would not fit
    # (8,192 positions: 8.6 GB); each block meets every key under its mask
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


def mlp(w, m, p):
    return (jax.nn.silu(m @ w[p + "gate_proj.weight"])
            * (m @ w[p + "up_proj.weight"])) @ w[p + "down_proj.weight"]


def block(w, h, sizes, p, mixer):
    """One decoder block on h [n, s, hidden] under the parameter prefix p."""
    eps, m = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    a = _rms(h, w[p + "input_layernorm.weight"], eps)
    if mixer == "mamba":
        h = h + m * mamba(w, a, sizes, p + "mamba.")
    else:
        h = h + m * attention(w, a, sizes, p + "self_attn.")
    a = _rms(h, w[p + "post_attention_layernorm.weight"], eps)
    return h + m * mlp(w, a, p + "shared_mlp.")


def embed(w, input_ids, sizes, prefix=""):
    return sizes["embedding_multiplier"] * w[
        prefix + "embed_tokens.weight"][input_ids]


def head(w, h, sizes, prefix=""):
    """The final norm and the tied head: the logits [n, s, vocab]."""
    return (_rms(h, w[prefix + "norm.weight"], sizes["rms_norm_eps"])
            @ w[prefix + "embed_tokens.weight"].T) / sizes["logits_scaling"]


def cross_entropy(logits, input_ids):
    """Position i's label is token i + 1; a row's last position predicts
    nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, input_ids[:, 1:, None], axis=-1))


def _forward(w, input_ids, sizes, prefix):
    h = embed(w, input_ids, sizes, prefix)
    for i, mixer in enumerate(layer_types(sizes)):
        h = block(w, h, sizes, f"{prefix}layers.{i}.", mixer)
    return head(w, h, sizes, prefix)


def _precision(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


def _f32(w):
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def forward(w, input_ids, sizes, prefix="", precision="highest"):
    """[n, s] token ids -> the logits [n, s, vocab]."""
    with _precision(precision):
        return _forward(_f32(w), input_ids, sizes, prefix)


def outputs(w, input_ids, sizes, prefix="", precision="highest"):
    """One pass: (logits, the loss)."""
    with _precision(precision):
        logits = _forward(_f32(w), input_ids, sizes, prefix)
        return logits, cross_entropy(logits, input_ids)


def loss(w, input_ids, sizes, prefix="", precision="highest"):
    return outputs(w, input_ids, sizes, prefix, precision)[1]
