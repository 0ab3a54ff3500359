"""Plain float32 forward of BERT (Devlin et al. 2018; the layer equations
of google-research/bert modeling.py), in straightforward ``jax.numpy`` with
no framework, kernel, cache or batching. Weights come as a dict under the
framework's parameter names (as a checkpoint would name them); Linear
weights are [in, out].

Departures from the source, the same as the configuration's file lists:
layer-norm eps is 1e-5; MLM logits are computed only at the masked
positions; the NSP head is left out.

On a TPU a float32 matmul runs in bf16 passes unless the precision is
raised, so every caller runs this under
``jax.default_matmul_precision("highest")`` (``forward_*`` set it
themselves).
"""
import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def encoder(w, input_ids, sizes, prefix="bert."):
    """[n, s] token ids -> [n, s, hidden] (post-LN encoder, eval mode:
    no dropout; token type 0 everywhere; positions 0..s-1)."""
    n, s = input_ids.shape
    heads = sizes["num_attention_heads"]
    p = prefix
    x = (w[p + "embeddings.word_embeddings.weight"][input_ids]
         + w[p + "embeddings.position_embeddings.weight"][jnp.arange(s)][None]
         + w[p + "embeddings.token_type_embeddings.weight"][0][None, None])
    x = _layer_norm(x, w[p + "embeddings.layer_norm.weight"],
                    w[p + "embeddings.layer_norm.bias"])
    hidden = x.shape[-1]
    d = hidden // heads
    for i in range(sizes["num_hidden_layers"]):
        lp = f"{p}encoder.layers.{i}."

        def proj(name, t):
            return t @ w[lp + name + ".weight"] + w[lp + name + ".bias"]

        def split(t):
            return t.reshape(n, s, heads, d).transpose(0, 2, 1, 3)

        q, k, v = (split(proj("self_attn." + nm, x))
                   for nm in ("q_proj", "k_proj", "v_proj"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, s, hidden)
        x = _layer_norm(x + proj("self_attn.out_proj", ctx),
                        w[lp + "norm1.weight"], w[lp + "norm1.bias"])
        ffn = proj("linear2", _gelu(proj("linear1", x)))
        x = _layer_norm(x + ffn, w[lp + "norm2.weight"], w[lp + "norm2.bias"])
    return x


def forward_mlm(w, input_ids, masked_positions, sizes):
    """MLM logits [n, p, vocab] at the masked positions (tied decoder)."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        seq = encoder(w, input_ids, sizes)
        picked = jnp.take_along_axis(seq, masked_positions[..., None], axis=1)
        h = picked @ w["cls.transform.weight"] + w["cls.transform.bias"]
        h = _layer_norm(_gelu(h), w["cls.layer_norm.weight"],
                        w["cls.layer_norm.bias"])
        return (h @ w["bert.embeddings.word_embeddings.weight"].T
                + w["cls.decoder_bias"])


def forward_pooled(w, input_ids, sizes, prefix="bert."):
    """The pooled vector [n, hidden]: tanh(dense(first token))."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        seq = encoder(w, input_ids, sizes, prefix)
        return jnp.tanh(seq[:, 0] @ w[prefix + "pooler.dense.weight"]
                        + w[prefix + "pooler.dense.bias"])
