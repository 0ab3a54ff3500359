"""Transformer model family for the training benchmarks.

BERT matches the PaddleNLP/ERNIE architecture the north-star names
(BASELINE.json config 3); GPT/Llama are the stretch decoder family
(config 5). Built entirely on paddle_tpu.nn layers so they exercise the
framework's own transformer stack (nn/layers/transformer.py ->
Pallas flash attention on TPU).
"""
import contextlib
import math

import numpy as np

from .. import nn
from ..nn import functional as F
from ..ops.attention import rotary as _rope


class BertEmbeddings(nn.Layer):
    def __init__(self, vocab_size, hidden_size, max_position_embeddings=512,
                 type_vocab_size=2, hidden_dropout_prob=0.1):
        super().__init__()
        self.word_embeddings = nn.Embedding(vocab_size, hidden_size)
        self.position_embeddings = nn.Embedding(max_position_embeddings, hidden_size)
        self.token_type_embeddings = nn.Embedding(type_vocab_size, hidden_size)
        self.layer_norm = nn.LayerNorm(hidden_size)
        self.dropout = nn.Dropout(hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from .. import tensor as pt

        if position_ids is None:
            position_ids = pt.arange(input_ids.shape[1], dtype="int64")
            position_ids = pt.expand(pt.unsqueeze(position_ids, 0),
                                     [input_ids.shape[0], input_ids.shape[1]])
        if token_type_ids is None:
            token_type_ids = pt.zeros_like(input_ids)
        emb = (self.word_embeddings(input_ids) +
               self.position_embeddings(position_ids) +
               self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(emb))


class BertPooler(nn.Layer):
    def __init__(self, hidden_size):
        super().__init__()
        self.dense = nn.Linear(hidden_size, hidden_size)

    def forward(self, hidden_states):
        return F.tanh(self.dense(hidden_states[:, 0]))


class BertModel(nn.Layer):
    """BERT-base default config (12L, 768H, 12 heads)."""

    def __init__(self, vocab_size=30522, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=3072,
                 hidden_act="gelu", hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, max_position_embeddings=512,
                 type_vocab_size=2, initializer_range=0.02, pad_token_id=0,
                 with_pool=True):
        super().__init__()
        self.embeddings = BertEmbeddings(vocab_size, hidden_size,
                                         max_position_embeddings, type_vocab_size,
                                         hidden_dropout_prob)
        enc_layer = nn.TransformerEncoderLayer(
            hidden_size, num_attention_heads, intermediate_size,
            dropout=hidden_dropout_prob, activation=hidden_act,
            attn_dropout=attention_probs_dropout_prob, act_dropout=0.0)
        self.encoder = nn.TransformerEncoder(enc_layer, num_hidden_layers)
        self.pooler = BertPooler(hidden_size) if with_pool else None
        self.hidden_size = hidden_size
        self.vocab_size = vocab_size

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        emb = self.embeddings(input_ids, token_type_ids, position_ids)
        seq = self.encoder(emb, attention_mask)
        if self.pooler is not None:
            return seq, self.pooler(seq)
        return seq


class BertLMPredictionHead(nn.Layer):
    def __init__(self, hidden_size, vocab_size, embedding_weights=None):
        super().__init__()
        self.transform = nn.Linear(hidden_size, hidden_size)
        self.layer_norm = nn.LayerNorm(hidden_size)
        self.decoder_weight = embedding_weights  # tied
        self.decoder_bias = self.create_parameter([vocab_size], is_bias=True)

    def forward(self, hidden_states):
        from .. import tensor as pt

        x = self.layer_norm(F.gelu(self.transform(hidden_states)))
        logits = pt.matmul(x, self.decoder_weight, transpose_y=True) + \
            self.decoder_bias
        return logits


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (the ERNIE/BERT pretraining benchmark model)."""

    def __init__(self, bert=None, **bert_kwargs):
        super().__init__()
        self.bert = bert or BertModel(**bert_kwargs)
        self.cls = BertLMPredictionHead(
            self.bert.hidden_size, self.bert.vocab_size,
            self.bert.embeddings.word_embeddings.weight)
        self.nsp = nn.Linear(self.bert.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        """masked_positions: optional [B, P] int positions of the masked
        tokens; when given, only those rows go through the vocab
        projection (reference: PaddleNLP BertPretrainingHeads gathers
        masked_positions before the decoder matmul — at 15% masking this
        cuts the 30k-vocab logits work ~6x)."""
        seq, pooled = self.bert(input_ids, token_type_ids,
                                attention_mask=attention_mask)
        if masked_positions is not None:
            from .. import tensor as pt

            idx = pt.unsqueeze(masked_positions, -1)  # [B, P, 1]
            seq = pt.take_along_axis(seq, idx, axis=1)  # [B, P, H]
        return self.cls(seq), self.nsp(pooled)


def bert_pretraining_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                          ignore_index=-100):
    """Masked-LM + NSP loss (pure Tensor ops; reference PaddleNLP
    BertPretrainingCriterion semantics)."""
    mlm_loss = F.cross_entropy(mlm_logits, mlm_labels, ignore_index=ignore_index,
                               reduction="mean", axis=-1)
    nsp_loss = F.cross_entropy(nsp_logits, nsp_labels, reduction="mean")
    return mlm_loss + nsp_loss


class GPTDecoderLayer(nn.Layer):
    def __init__(self, hidden_size, num_heads, intermediate_size, dropout=0.0,
                 act="gelu"):
        super().__init__()
        self.ln1 = nn.LayerNorm(hidden_size)
        self.attn = nn.MultiHeadAttention(hidden_size, num_heads, dropout)
        self.ln2 = nn.LayerNorm(hidden_size)
        self.fc1 = nn.Linear(hidden_size, intermediate_size)
        self.fc2 = nn.Linear(intermediate_size, hidden_size)
        self.act = act
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None):
        h = self.ln1(x)
        x = x + self.attn(h, h, h, mask)
        h = self.ln2(x)
        x = x + self.dropout(self.fc2(getattr(F, self.act)(self.fc1(h))))
        return x


class GPTModel(nn.Layer):
    """Pre-norm causal decoder (GPT-2 style)."""

    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_seq_len=1024,
                 dropout=0.0):
        super().__init__()
        intermediate_size = intermediate_size or 4 * hidden_size
        self.wte = nn.Embedding(vocab_size, hidden_size)
        self.wpe = nn.Embedding(max_seq_len, hidden_size)
        self.blocks = nn.LayerList([
            GPTDecoderLayer(hidden_size, num_heads, intermediate_size, dropout)
            for _ in range(num_layers)])
        self.ln_f = nn.LayerNorm(hidden_size)
        self.max_seq_len = max_seq_len

    def forward(self, input_ids):
        from .. import tensor as pt

        b, t = input_ids.shape
        pos = pt.expand(pt.unsqueeze(pt.arange(t, dtype="int64"), 0), [b, t])
        x = self.wte(input_ids) + self.wpe(pos)
        mask = nn.Transformer.generate_square_subsequent_mask(t)
        for blk in self.blocks:
            x = blk(x, mask)
        x = self.ln_f(x)
        return pt.matmul(x, self.wte.weight, transpose_y=True)

    def generate(self, input_ids, **kwargs):
        from .generation import generate as _generate

        return _generate(self, input_ids, **kwargs)


class RMSNorm(nn.Layer):
    def __init__(self, hidden_size, eps=1e-6):
        super().__init__()
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=nn.initializer.Constant(1.0))
        self.eps = eps

    def forward(self, x):
        from ..core.dispatch import apply_op

        return apply_op("rms_norm", rms_norm, x, self.weight, eps=self.eps)


def rms_norm(x, w, *, eps=1e-6):
    """Shared RMSNorm kernel (also used by the cached decode path in
    generation.py — single source of truth for the Llama math)."""
    import jax
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def _merge_heads(o):
    """[B, H, T, d] -> [B, T, H x d]."""
    b, h, t, d = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * d)


class LlamaAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads=None):
        super().__init__()
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = hidden_size // num_heads
        self.q_proj = nn.Linear(hidden_size, hidden_size, bias_attr=False)
        self.k_proj = nn.Linear(hidden_size, self.num_kv_heads * self.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(hidden_size, self.num_kv_heads * self.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(hidden_size, hidden_size, bias_attr=False)

    def forward(self, x):
        import jax.numpy as jnp

        from ..core.dispatch import apply_op
        from ..ops.attention import scaled_dot_product_attention as _sdpa

        def _qkv(x, wq, wk, wv, *, nh, nkv, hd):
            b, t, _ = x.shape
            q = (x @ wq).reshape(b, t, nh, hd).transpose(0, 2, 1, 3)
            k = (x @ wk).reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
            v = (x @ wv).reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
            q = _rope(q)
            k = _rope(k)
            if nkv != nh:
                rep = nh // nkv
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            return q, k, v

        q, k, v = apply_op("llama_qkv_rope", _qkv, x, self.q_proj.weight,
                           self.k_proj.weight, self.v_proj.weight,
                           nh=self.num_heads, nkv=self.num_kv_heads,
                           hd=self.head_dim)
        # causal attention through the dispatching sdpa: Pallas flash
        # kernel on TPU (blockwise softmax), XLA-fused jnp path elsewhere
        out = _sdpa(q, k, v, is_causal=True, training=self.training)

        def _merge(out, wo, *, nh, hd):
            b, h, t, d = out.shape
            return out.transpose(0, 2, 1, 3).reshape(b, t, nh * hd) @ wo

        return apply_op("llama_attn_out", _merge, out, self.o_proj.weight,
                        nh=self.num_heads, hd=self.head_dim)


class LlamaMLP(nn.Layer):
    """SwiGLU: ``down(silu(gate(x)) * up(x))``, no biases."""

    def __init__(self, hidden_size, intermediate_size, weight_attr=None):
        super().__init__()

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.gate_proj = proj(hidden_size, intermediate_size)
        self.up_proj = proj(hidden_size, intermediate_size)
        self.down_proj = proj(intermediate_size, hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class Relu2MLP(nn.Layer):
    """Nemotron-H's feed-forward part: ``down(relu(up(x))^2)``, two
    matrices, no gate, no biases."""

    def __init__(self, hidden_size, intermediate_size, weight_attr=None):
        super().__init__()

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.up_proj = proj(hidden_size, intermediate_size)
        self.down_proj = proj(intermediate_size, hidden_size)

    def forward(self, x):
        h = F.relu(self.up_proj(x))
        return self.down_proj(h * h)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, hidden_size, num_heads, intermediate_size, num_kv_heads=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size)
        self.self_attn = LlamaAttention(hidden_size, num_heads, num_kv_heads)
        self.post_attention_layernorm = RMSNorm(hidden_size)
        self.mlp = LlamaMLP(hidden_size, intermediate_size)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    """Llama-2 architecture (7B default dims; shrink via kwargs for tests).

    ``tensor_parallel=True`` stamps Megatron-style shardings onto the
    weights (q/k/v/gate/up column-parallel over the 'mp' mesh axis,
    o/down row-parallel, vocab-parallel embedding + lm_head) — the
    TPU-native tensor parallelism (SURVEY §7): shard specs go in,
    XLA GSPMD propagates them through the attention/MLP einsums and
    inserts the psum on row-parallel contractions, replacing the
    reference's explicit c_identity/c_allreduce op pairs
    (tensor_parallel_optimizer.py:134-211)."""

    def __init__(self, vocab_size=32000, hidden_size=4096, num_layers=32,
                 num_heads=32, intermediate_size=11008, num_kv_heads=None,
                 max_seq_len=4096, tensor_parallel=False):
        super().__init__()
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size)
        self.layers = nn.LayerList([
            LlamaDecoderLayer(hidden_size, num_heads, intermediate_size,
                              num_kv_heads)
            for _ in range(num_layers)])
        self.norm = RMSNorm(hidden_size)
        self.lm_head = nn.Linear(hidden_size, vocab_size, bias_attr=False)
        if tensor_parallel:
            self._stamp_tensor_parallel()

    def _stamp_tensor_parallel(self, axis="mp"):
        from ..distributed.spmd import P

        self.embed_tokens.weight.mp_spec = P(axis, None)   # vocab-parallel
        self.lm_head.weight.mp_spec = P(None, axis)
        for layer in self.layers:
            attn, mlp = layer.self_attn, layer.mlp
            for col in (attn.q_proj, attn.k_proj, attn.v_proj,
                        mlp.gate_proj, mlp.up_proj):
                col.weight.mp_spec = P(None, axis)          # shard heads/ffn
            for row in (attn.o_proj, mlp.down_proj):
                row.weight.mp_spec = P(axis, None)          # psum on contract

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.lm_head(self.norm(x))

    def generate(self, input_ids, use_cache=True, **kwargs):
        """KV-cached scan decode by default; use_cache=False falls back to
        the generic full-width path (cross-checks the cache in tests)."""
        from .generation import generate as _generate
        from .generation import llama_generate as _llama_generate

        # early-eos stopping needs host-side control flow -> generic path
        if (use_cache and kwargs.get("eos_token_id") is None
                and kwargs.get("max_length") is None):
            kwargs.pop("eos_token_id", None)
            kwargs.pop("max_length", None)
            kwargs.pop("pad_token_id", None)
            return _llama_generate(self, input_ids, **kwargs)
        return _generate(self, input_ids, **kwargs)


class OlmoeAttention(nn.Layer):
    """OLMoE's attention: bias-free projections, RMSNorm over the whole
    hidden-wide q and k before the split into heads (QK-norm), rotate-half
    RoPE, causal attention on [B, H, T, D] through the dispatching sdpa
    (the streaming flash kernel at long sequences, like LlamaAttention)."""

    def __init__(self, hidden_size, num_heads, rms_norm_eps=1e-5,
                 rope_theta=10000.0, weight_attr=None):
        super().__init__()
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.rope_theta = float(rope_theta)

        def proj():
            return nn.Linear(hidden_size, hidden_size,
                             weight_attr=weight_attr, bias_attr=False)

        self.q_proj, self.k_proj, self.v_proj = proj(), proj(), proj()
        self.o_proj = proj()
        self.q_norm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.k_norm = RMSNorm(hidden_size, eps=rms_norm_eps)

    def forward(self, x):
        from ..core.dispatch import apply_op
        from ..ops.attention import scaled_dot_product_attention as _sdpa

        def _heads(q, k, v, *, nh, hd, base):
            def split(t):
                b, s, _ = t.shape
                return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

            return (_rope(split(q), base, pairing="half"),
                    _rope(split(k), base, pairing="half"), split(v))

        q, k, v = apply_op(
            "olmoe_heads_rope", _heads, self.q_norm(self.q_proj(x)),
            self.k_norm(self.k_proj(x)), self.v_proj(x),
            nh=self.num_heads, hd=self.head_dim, base=self.rope_theta)
        out = _sdpa(q, k, v, is_causal=True, training=self.training)
        return self.o_proj(apply_op("merge_heads", _merge_heads, out))


class OlmoeDecoderLayer(nn.Layer):
    """Pre-norm block: attention, then the sparse-expert FFN
    (``incubate.moe.MoELayer``: SwiGLU experts, bias-free softmax router,
    top-k weights not renormalised, no shared expert)."""

    def __init__(self, hidden_size, num_heads, intermediate_size,
                 num_experts, num_experts_per_tok, rms_norm_eps=1e-5,
                 rope_theta=10000.0, norm_topk_prob=False,
                 router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
                 weight_attr=None):
        super().__init__()
        from ..incubate.moe import MoELayer

        self.input_layernorm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.self_attn = OlmoeAttention(hidden_size, num_heads, rms_norm_eps,
                                        rope_theta, weight_attr)
        self.post_attention_layernorm = RMSNorm(hidden_size,
                                                eps=rms_norm_eps)
        self.mlp = MoELayer(
            hidden_size, intermediate_size, num_experts,
            top_k=num_experts_per_tok, activation="swiglu", gate_bias=False,
            norm_topk_prob=norm_topk_prob, aux_weight=router_aux_loss_coef,
            z_loss_weight=router_z_loss_coef, weight_attr=weight_attr)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class OlmoeModel(nn.Layer):
    """OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; HF ``olmoe``):
    a decoder whose every layer is attention with QK-norm plus a
    dropless sparse-expert FFN. Defaults are OLMoE-1B-7B's published
    sizes; every weight starts Normal(0, ``initializer_range``).

    ``forward`` returns the logits [B, T, vocab]. A training loss over a
    large vocabulary should not hold them: ``features`` returns the
    final-normed hidden states, to be given with ``lm_head.weight`` to
    ``F.linear_cross_entropy``. The expert layers' load-balancing and
    z-losses reach a compiled step through ``nn.aux_loss``."""

    def __init__(self, vocab_size=50304, hidden_size=2048,
                 num_hidden_layers=16, num_attention_heads=16,
                 intermediate_size=1024, num_experts=64,
                 num_experts_per_tok=8, rms_norm_eps=1e-5,
                 rope_theta=10000.0, norm_topk_prob=False,
                 router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
                 initializer_range=0.02):
        super().__init__()
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            OlmoeDecoderLayer(
                hidden_size, num_attention_heads, intermediate_size,
                num_experts, num_experts_per_tok, rms_norm_eps, rope_theta,
                norm_topk_prob, router_aux_loss_coef, router_z_loss_coef,
                weight_attr=attr())
            for _ in range(num_hidden_layers)])
        self.norm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)

    def features(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


def _held_range(held_heads, num_heads, fits=None, what="no range"):
    """``held_heads=(first, count)`` as two ints: one chip's range of a
    layer's ``num_heads`` heads, and — where the layer's heads come in
    units (groups, key/value heads) — one that ``fits(first, count)``;
    ``what`` says in the error what such a range is."""
    first, count = (int(v) for v in held_heads)
    if not (0 <= first < first + count <= num_heads
            and (fits is None or fits(first, count))):
        raise ValueError(f"held_heads=(first, count)={held_heads!r} is "
                         f"{what} of the {num_heads} heads")
    return first, count


class MLAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2/V3; HF ``DeepseekV3Attention``)
    in its training form — the latent is EXPANDED to every head's keys and
    values; the absorbed form, which folds ``kv_b_proj`` into the query and
    the output, is a decode-time rewrite and is not built —: q through a
    low-rank pair with a RMSNorm between, ``[c_kv ; k_r] = x W_kva`` with
    c_kv normed and expanded to each head's (no-position key, value), RoPE
    on each head's
    ``rope_dim`` query features and on the ONE ``rope_dim``-wide k_r that
    all heads share, then a causal core whose keys (``nope_dim + rope_dim``)
    are wider than its values (``v_dim``), through the dispatching sdpa (the
    streaming kernel takes the two widths as they are).
    ``q_lora_rank=None``: one q projection (``q_proj``), no norm between.
    ``rope=False`` (Kimi Linear's ``mla_use_nope``): nothing is rotated, the
    ``rope_dim``-wide shared key part is broadcast and concatenated as it
    is; positions then reach the layer only through the causal mask.

    ``rope_scaling`` (a config's group of ``type: yarn``): the rotation's
    frequencies are YaRN's blended table, cos and sin carry its attention
    factor and the softmax scale ``(nope_dim + rope_dim) ** -0.5`` its
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` (``ops.attention
    .yarn_rope``); None rotates by ``rope_theta``'s one table at the plain
    scale, and lowers to the program it always did.

    ``held_heads=(first, count)`` builds ONE CHIP'S SHARE of a layer whose
    ``num_heads`` heads are divided over chips (tensor parallelism without
    its exchange): those heads' columns of ``q_b_proj`` (or ``q_proj``) and
    of ``kv_b_proj`` and their rows of ``o_proj``; the low-rank ``q_a_proj``
    / ``kv_a_proj_with_mqa``, both latent norms and the one shared k_r are
    every head's and are whole on every chip. ``o_proj`` gives a PARTIAL SUM
    over the heads held: what the absent chips would add is left out, and
    nothing stands in for them or their all-reduce. Scopes: ``mla.q`` /
    ``.kv`` / ``.rope`` / ``.core`` / ``.out``."""

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rms_norm_eps=1e-6, rope_theta=10000.0, weight_attr=None,
                 rope=True, held_heads=None, rope_scaling=None):
        super().__init__()
        self.held_heads = None
        if held_heads is not None:
            self.held_heads = _held_range(held_heads, num_heads)
            num_heads = self.held_heads[1]   # what is built and run here
        self.num_heads = num_heads
        self.nope_dim, self.rope_dim = qk_nope_head_dim, qk_rope_head_dim
        self.v_dim = v_head_dim
        self.kv_rank = kv_lora_rank
        self.rope_theta = float(rope_theta)
        self.rope = bool(rope)
        # static arguments of the rotation and the core that only a scaled
        # RoPE gives: without one both calls are the ones they always were
        self._rope_args, self._core_args = {}, {}
        if rope_scaling is not None:
            from ..ops.attention import yarn_rope

            inv_freq, mscale, softmax = yarn_rope(
                rope_scaling, qk_rope_head_dim, self.rope_theta)
            self._rope_args = {"inv_freq": inv_freq, "mscale": mscale}
            self._core_args = {"scale": softmax * (
                qk_nope_head_dim + qk_rope_head_dim) ** -0.5}

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        qk = qk_nope_head_dim + qk_rope_head_dim
        self.q_lora_rank = q_lora_rank
        if q_lora_rank is None:
            self.q_proj = proj(hidden_size, num_heads * qk)
        else:
            self.q_a_proj = proj(hidden_size, q_lora_rank)
            self.q_a_layernorm = RMSNorm(q_lora_rank, eps=rms_norm_eps)
            self.q_b_proj = proj(q_lora_rank, num_heads * qk)
        self.kv_a_proj_with_mqa = proj(hidden_size,
                                       kv_lora_rank + qk_rope_head_dim)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, eps=rms_norm_eps)
        self.kv_b_proj = proj(kv_lora_rank,
                              num_heads * (qk_nope_head_dim + v_head_dim))
        self.o_proj = proj(num_heads * v_head_dim, hidden_size)

    def forward(self, x):
        import jax
        import jax.numpy as jnp

        from ..core.dispatch import apply_op
        from ..ops.attention import scaled_dot_product_attention as _sdpa

        def _split(kv_a, *, rank):
            return kv_a[..., :rank], kv_a[..., rank:]

        # every size rides the static arguments: eager dispatch caches an
        # op's program by name and static arguments, and a share's head
        # count is not the whole layer's
        def _heads(q, kv, k_r, *, nh, nope, rope, dv, base, rotate,
                   **scaled):
            b, s, _ = q.shape
            q = q.reshape(b, s, nh, nope + rope).transpose(0, 2, 1, 3)
            kv = kv.reshape(b, s, nh, nope + dv).transpose(0, 2, 1, 3)
            k_r = k_r[:, None]
            if rotate:
                # interleaved pairs (2i, 2i + 1) as the checkpoint stores
                # them (rope_interleave); one rotated k_r serves every head
                q = jnp.concatenate(
                    [q[..., :nope], _rope(q[..., nope:], base, **scaled)],
                    axis=-1)
                k_r = _rope(k_r, base, **scaled)
            k_r = jnp.broadcast_to(k_r, (b, nh, s, rope))
            return (q, jnp.concatenate([kv[..., :nope], k_r], axis=-1),
                    kv[..., nope:])

        with jax.named_scope("mla.q"):
            if self.q_lora_rank is None:
                q = self.q_proj(x)
            else:
                q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        with jax.named_scope("mla.kv"):
            c_kv, k_r = apply_op("mla_split", _split,
                                 self.kv_a_proj_with_mqa(x),
                                 rank=self.kv_rank)
            kv = self.kv_b_proj(self.kv_a_layernorm(c_kv))
        with jax.named_scope("mla.rope"):
            q, k, v = apply_op("mla_heads_rope", _heads, q, kv, k_r,
                               nh=self.num_heads, nope=self.nope_dim,
                               rope=self.rope_dim, dv=self.v_dim,
                               base=self.rope_theta, rotate=self.rope,
                               **self._rope_args)
        with jax.named_scope("mla.core"):
            out = _sdpa(q, k, v, is_causal=True, training=self.training,
                        **self._core_args)
        with jax.named_scope("mla.out"):
            return self.o_proj(apply_op("merge_heads", _merge_heads, out))


class JoyAIDecoderLayer(nn.Layer):
    """Pre-norm block of the DeepSeek-V3 family and of Kimi Linear: a token
    mixer by layer type — latent attention (``mixer='mla'``) or Kimi Delta
    Attention (``'kda'``, sized by ``cfg['kda']``) — then a dense SwiGLU (the
    leading layers) or the expert layer: sigmoid router with a selection
    bias, renormalised top-k times ``routed_scaling_factor``, a shared
    expert, and the held range of the routed experts."""

    def __init__(self, cfg, dense, weight_attr=None, mixer="mla"):
        super().__init__()
        from ..incubate.moe import MoELayer

        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.input_layernorm = RMSNorm(hidden, eps=eps)
        if mixer == "kda":
            self.self_attn = KimiDeltaAttention(
                hidden, rms_norm_eps=eps, weight_attr=weight_attr,
                **cfg["kda"])
        else:
            self.self_attn = MLAttention(
                hidden, cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"], eps,
                cfg["rope_theta"], weight_attr, rope=cfg.get("rope", True),
                held_heads=cfg.get("held_attention_heads"),
                rope_scaling=cfg.get("rope_scaling"))
        self.post_attention_layernorm = RMSNorm(hidden, eps=eps)
        if dense:
            self.mlp = LlamaMLP(hidden, cfg["intermediate_size"],
                                weight_attr)
        else:
            self.mlp = MoELayer(
                hidden, cfg["moe_intermediate_size"],
                cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
                activation="swiglu", gate_bias=False,
                norm_topk_prob=cfg["norm_topk_prob"], scoring="sigmoid",
                select_bias=True,
                bias_update_speed=cfg["bias_update_speed"],
                routed_scale=cfg["routed_scaling_factor"],
                shared_width=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"],
                held=cfg["held_experts"],
                held_rows_factor=cfg["held_rows_factor"],
                aux_weight=cfg["balance_loss_weight"],
                weight_attr=weight_attr)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class _BlockwiseModel(nn.Layer):
    """What the decoders that run block by block share: a block called
    plainly or under ``fleet.utils.recompute`` (``use_recompute``, in a
    training trace), and the taps a block-by-block comparison feeds on."""

    def __init__(self, use_recompute):
        super().__init__()
        self.use_recompute = bool(use_recompute)
        self._tap = None

    def _block(self, block, *inputs):
        if self.use_recompute and self.training:
            from ..distributed.fleet.utils import recompute

            out = recompute(block, *inputs)
        else:
            out = block(*inputs)
        if self._tap is not None:
            self._tap.append((block, inputs, out))
        return out

    @contextlib.contextmanager
    def tapped(self):
        """Collect (block, its inputs, its output) of every decoder block
        and MTP module called inside."""
        self._tap = taps = []
        try:
            yield taps
        finally:
            self._tap = None


class _MTPDecoder(_BlockwiseModel):
    """What the decoders with multi-token-prediction modules share
    (``embed_tokens``, ``layers``, ``norm``, an untied ``lm_head``, ``mtp``):
    ``forward`` gives the main logits; a training loss should not hold them:
    ``training_features`` gives the final-normed hidden states of the main
    model and of every MTP module, for ``mtp_lm_loss`` with
    ``lm_head.weight``."""

    def _trunk(self, input_ids):
        """The last block's output, before the final norm."""
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return x

    def features(self, input_ids):
        return self.norm(self._trunk(input_ids))

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))

    def training_features(self, input_ids):
        """(final-normed hidden states, [each MTP module's]): position i of
        module d's output predicts token i + d + 2. The modules chain: the
        first reads the last block's output (before the final norm), each
        later one the module before it, with the embedding of the token one
        further on; a row's last positions see the row's last token again
        and carry no label."""
        from .. import tensor as pt

        h = self._trunk(input_ids)
        main, heads, ids = self.norm(h), [], input_ids
        for module in self.mtp:
            ids = pt.concat([ids[:, 1:], ids[:, -1:]], axis=1)
            h, normed = self._block(module, h, self.embed_tokens(ids))
            heads.append(normed)
        return main, heads


class JoyAIFlashModel(_MTPDecoder):
    """JoyAI-LLM-Flash (HF ``joyai_llm_flash``; the DeepSeek-V3 family's
    equations): ``first_k_dense_replace`` dense blocks, then expert blocks,
    every one with latent attention; a final norm and an untied head; and
    ``num_nextn_predict_layers`` multi-token-prediction modules that share
    the embedding and the head. Defaults are the published sizes.

    ``held_experts=(first, count)`` gives every expert layer this chip's
    range of the routed experts (default: all of them). ``use_recompute``
    runs each block under ``fleet.utils.recompute`` in a traced step.

    ``forward`` gives the main logits. A training loss should not hold
    them: ``training_features`` gives the final-normed hidden states of the
    main model and of the MTP module, for ``mtp_lm_loss`` with
    ``lm_head.weight``."""

    def __init__(self, vocab_size=129280, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 intermediate_size=7168, moe_intermediate_size=768,
                 n_routed_experts=256, num_experts_per_tok=8,
                 n_shared_experts=1, first_k_dense_replace=1,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rms_norm_eps=1e-6,
                 rope_theta=32000000.0, norm_topk_prob=True,
                 routed_scaling_factor=2.5, num_nextn_predict_layers=1,
                 bias_update_speed=0.001, balance_loss_weight=0.0,
                 initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        cfg = dict(
            hidden_size=hidden_size, num_attention_heads=num_attention_heads,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=n_routed_experts,
            num_experts_per_tok=num_experts_per_tok,
            n_shared_experts=n_shared_experts, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rms_norm_eps=rms_norm_eps, rope_theta=rope_theta,
            norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor,
            bias_update_speed=bias_update_speed,
            balance_loss_weight=balance_loss_weight,
            held_experts=None if held_experts is None else tuple(held_experts),
            held_rows_factor=held_rows_factor)
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            JoyAIDecoderLayer(cfg, dense=i < first_k_dense_replace,
                              weight_attr=attr())
            for i in range(num_hidden_layers)])
        self.norm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)
        self.mtp = nn.LayerList([
            MultiTokenPredictor(
                hidden_size, rms_norm_eps,
                lambda: JoyAIDecoderLayer(cfg, dense=False,
                                          weight_attr=attr()),
                weight_attr=attr())
            for _ in range(num_nextn_predict_layers)])


class MultiTokenPredictor(nn.Layer):
    """One multi-token-prediction module (DeepSeek-V3 report, section 2.2):
    ``h' = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``, the model's
    own block(s), and the norm before the shared head. ``block`` is what
    the module wraps — a layer (an ``nn.Sequential`` of several), or a
    function of no argument that builds it, called here after ``eh_proj``
    so that a seed gives every parameter the value it had when the module
    built its block itself: JoyAI's one expert block, Nemotron 3's
    attention layer and expert layer (``mtp_hybrid_override_pattern``
    ``*E``). Returns (the block's output, for the next module; its normed
    form, for the head)."""

    def __init__(self, hidden, eps, block, weight_attr=None):
        super().__init__()
        self.hnorm = RMSNorm(hidden, eps=eps)
        self.enorm = RMSNorm(hidden, eps=eps)
        self.eh_proj = nn.Linear(2 * hidden, hidden, weight_attr=weight_attr,
                                 bias_attr=False)
        self.block = block if isinstance(block, nn.Layer) else block()
        self.norm = RMSNorm(hidden, eps=eps)

    def forward(self, h, emb):
        from .. import tensor as pt

        x = self.eh_proj(pt.concat([self.hnorm(h), self.enorm(emb)],
                                   axis=-1))
        x = self.block(x)
        return x, self.norm(x)


def mtp_lm_loss(hidden, mtp_hidden, head_weight, input_ids, mtp_weight=0.3):
    """``CE(main_i, t_{i+1}) + mtp_weight * mean_d CE(mtp_d_i, t_{i+d+2})``
    from the final hidden states, never holding a [tokens, vocab] array
    (``F.linear_cross_entropy`` on the one shared head): the label of
    position i is the token ``shift`` further on, the row's last ``shift``
    positions predict nothing. Returns (total, main term, MTP term)."""
    from .. import tensor as pt

    def shifted(shift):
        pad = pt.full_like(input_ids[:, :shift], -100)
        return pt.concat([input_ids[:, shift:], pad], axis=1)

    main = F.linear_cross_entropy(hidden, head_weight, shifted(1))
    if not mtp_hidden:
        return main, main, None
    terms = [F.linear_cross_entropy(h, head_weight, shifted(d + 2))
             for d, h in enumerate(mtp_hidden)]
    mtp = terms[0]
    for t in terms[1:]:
        mtp = mtp + t
    mtp = mtp / float(len(terms))
    return main + mtp * float(mtp_weight), main, mtp


#: Kimi-Linear-48B-A3B's layer pattern (1-indexed, as its config.json
#: writes it): three Kimi Delta Attention layers, then one of latent
#: attention, and latent attention again in the last layer
KIMI_LINEAR_ATTN = {
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}


# A KDA layer's element-wise stages run at [tokens, heads x head_dim] in
# float32: kept for a backward pass they would be a dozen 268 MB arrays a
# layer at 16,384 tokens. The decay and the output norm are each a
# ``jax.checkpoint`` of their own: a differentiated program keeps the
# stage's (bf16 or low-rank) inputs and rebuilds the float32 inside it;
# without differentiation it is the plain function. The convolution stage
# is ``ops.linear_attention.conv_streams``: where its Mosaic kernels run
# (a TPU, lane-wide heads) that float32 exists in VMEM only, forward and
# backward, and what a backward pass keeps is the stage's inputs by the
# op's own rule; elsewhere it is the same ``jax.checkpoint`` of XLA
# operations. Every stage stays on [B, T, H * d] streams, the tiling of the
# projections, the convolution and the scan's kernels: a head is a slice of
# the last axis, its statistics come from a lane sum in the kernels and
# from ``ops.linear_attention.head_rsqrt`` outside them, and no array is
# viewed as [.., H, d] (on a TPU that view is a physical relayout of 268
# MB, there and back).
def _gated_head_norm(o, gate, w, heads, *, eps, activation):
    """``w * RMSNorm_d(o) * activation(gate)`` a head, in float32, with the
    learned d-wide weight laid on every head's lanes; o and gate [B, T,
    H * d] -> the same shape in o's dtype."""
    import jax.numpy as jnp

    from ..ops.linear_attention import head_rsqrt

    of = o.astype(jnp.float32)
    normed = of * head_rsqrt(of, heads, eps=eps, mean=True) * jnp.tile(
        w.astype(jnp.float32), heads)
    return (normed * activation(gate.astype(jnp.float32))).astype(o.dtype)


def _kda_segments(wide, d):
    """``conv_streams``' segments of ``_kda_streams``' three streams."""
    return ((0, 0, wide, d ** -0.5), (1, 0, wide, 1.0), (2, 0, wide, None))


def _kda_streams(q, k, v, w_q, w_k, w_v, *, heads, eps, kernel="ask"):
    """The projected [B, T, H * d] streams, each through its causal
    depthwise convolution and SiLU, q and k then L2-normalised over a
    head's features (in float32), q scaled by d^-0.5: three [B, T, H * d]
    streams. ``kernel``: ``conv_kernel``'s answer, taken outside the op."""
    from ..ops.linear_attention import conv_streams

    wide, d = q.shape[-1], q.shape[-1] // heads
    return conv_streams((q, k, v), (w_q, w_k, w_v), _kda_segments(wide, d),
                        head=d, eps=eps, kernel=kernel)


def _kda_decay(low, w_up, a_log, dt_bias, *, heads):
    """g = -exp(A_log_h) softplus(low W + dt_bias) in float32: [B, T,
    H * d], the decay's logarithm per head and key channel (a head's factor
    repeated over its d lanes)."""
    import jax
    import jax.numpy as jnp

    def decay(low, w_up, a_log, dt_bias):
        f32 = jnp.float32
        z = jnp.dot(low.astype(f32), w_up.astype(f32)) + dt_bias.astype(f32)
        return -jnp.repeat(jnp.exp(a_log.astype(f32)),
                           z.shape[-1] // heads) * jax.nn.softplus(z)

    return jax.checkpoint(decay)(low, w_up, a_log, dt_bias)


def _kda_beta(x, w):
    import jax
    import jax.numpy as jnp

    return jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                  w.astype(jnp.float32)))


def _kda_gated_norm(o, gate, w, *, heads, eps):
    """sigmoid(gate) * RMSNorm_d(o) per head with the learned d-wide weight,
    in float32; [B, T, H * d] -> the same in o's dtype."""
    import jax

    def gated(o, gate, w):
        return _gated_head_norm(o, gate, w, heads, eps=eps,
                                activation=jax.nn.sigmoid)

    return jax.checkpoint(gated)(o, gate, w)


class KimiDeltaAttention(nn.Layer):
    """Kimi Delta Attention (Kimi Linear technical report, arXiv:2510.26692;
    HF ``kimi_linear``): q, k and v each through a projection, a causal
    depthwise convolution of ``short_conv_kernel_size`` taps and SiLU; q and
    k L2-normalised a head; a decay per head AND key channel from a
    low-rank pair, ``g = -exp(A_log) softplus(f_b(f_a(x)) + dt_bias)``; a
    write strength ``beta = sigmoid(b(x))`` a head; the gated delta rule's
    state recurrence (``ops.linear_attention``: the chunked scan from one
    sub-block of tokens on); and ``o_proj(sigmoid(g_b(g_a(x))) *
    RMSNorm_d(o))``. The convolution's multiply-adds, SiLU, the L2 norms,
    the decay, beta, the scan's state and the output norm are float32 under
    amp O1; the projections and the scan's large products take bf16
    operands. The stage between the projections and the scan (taps, SiLU,
    L2 norms) is one op, ``ops.linear_attention.conv_streams``: one Mosaic
    call a pass on a TPU at lane-wide heads, XLA operations elsewhere
    (``paddle_tpu_conv_streams_total{path}`` says which)."""

    def __init__(self, hidden_size, num_heads=32, head_dim=128,
                 short_conv_kernel_size=4, gate_rank=None, chunk=64,
                 rms_norm_eps=1e-5, l2_eps=1e-6, weight_attr=None):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.chunk, self.l2_eps = int(chunk), float(l2_eps)
        inner = num_heads * head_dim
        rank = gate_rank or head_dim

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        def conv():
            return nn.CausalDepthwiseConv1D(inner, short_conv_kernel_size,
                                            activation="silu")

        self.q_proj, self.k_proj, self.v_proj = (
            proj(hidden_size, inner) for _ in range(3))
        self.q_conv, self.k_conv, self.v_conv = conv(), conv(), conv()
        self.f_a_proj, self.f_b_proj = proj(hidden_size, rank), proj(rank,
                                                                     inner)
        # A = exp(A_log) ~ U(1, 16) a head; dt_bias the inverse softplus of
        # dt ~ exp(U(log 1e-3, log 1e-1)) a channel (the Mamba-2 convention)
        # named, so that an optimizer's apply_decay_param_fun can tell them
        # (neither is decayed where the model was trained)
        from ..framework.param_attr import ParamAttr

        def named(name, low, high):
            return ParamAttr(name=f"{self.full_name()}.{name}",
                             initializer=nn.initializer.Uniform(low, high))

        self.A_log = self.create_parameter(
            [num_heads], attr=named("A_log", 1.0, 16.0))
        self.A_log.set_value(np.log(np.asarray(self.A_log._value)))
        self.dt_bias = self.create_parameter(
            [inner], attr=named("dt_bias", math.log(1e-3), math.log(1e-1)))
        dt = np.exp(np.asarray(self.dt_bias._value, np.float64))
        self.dt_bias.set_value(dt + np.log(-np.expm1(-dt)))
        self.b_proj = proj(hidden_size, num_heads)
        self.g_a_proj, self.g_b_proj = proj(hidden_size, rank), proj(rank,
                                                                     inner)
        self.o_norm = RMSNorm(head_dim, eps=rms_norm_eps)
        self.o_proj = proj(inner, hidden_size)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.linear_attention import conv_kernel, gated_delta_rule

        with jax.named_scope("kda.proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with jax.named_scope("kda.conv"):
            q, k, v = apply_op(
                "kda_streams", _kda_streams, q, k, v, self.q_conv.weight,
                self.k_conv.weight, self.v_conv.weight,
                heads=self.num_heads, eps=self.l2_eps, kernel=conv_kernel(
                    q, self.q_conv.weight, _kda_segments(
                        q.shape[-1], self.head_dim), self.head_dim))
        with jax.named_scope("kda.gate"):
            g = apply_op("kda_decay", _kda_decay, self.f_a_proj(x),
                         self.f_b_proj.weight, self.A_log, self.dt_bias,
                         heads=self.num_heads)
            beta = apply_op("kda_beta", _kda_beta, x, self.b_proj.weight)
            gate = self.g_b_proj(self.g_a_proj(x))
        with jax.named_scope("kda.core"):
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope("kda.out"):
            return self.o_proj(apply_op(
                "kda_gated_norm", _kda_gated_norm, o, gate,
                self.o_norm.weight, heads=self.num_heads,
                eps=self.o_norm.eps))


class KimiLinearModel(_BlockwiseModel):
    """Kimi-Linear-48B-A3B (HF ``kimi_linear``): pre-norm blocks whose mixer
    goes by layer type — Kimi Delta Attention on ``linear_attn_config``'s
    ``kda_layers``, latent attention without positions (no q rank, nothing
    rotated) on its ``full_attn_layers``, both lists 1-indexed — and whose
    feed-forward is a dense SwiGLU in the first ``first_k_dense_replace``
    layers and the DeepSeek-V3 family's expert layer elsewhere; a final norm
    and an untied head; no MTP module. Defaults are the published sizes.

    ``held_experts=(first, count)`` gives every expert layer this chip's
    range of the routed experts; ``use_recompute`` runs each block under
    ``fleet.utils.recompute`` in a traced step. ``forward`` gives the
    logits; a training loss takes ``features`` and ``lm_head.weight`` to
    ``mtp_lm_loss`` (no MTP term) or ``F.linear_cross_entropy``."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, num_attention_heads=32,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 n_routed_experts=256, num_experts_per_tok=8,
                 n_shared_experts=1, first_k_dense_replace=1,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rms_norm_eps=1e-5,
                 norm_topk_prob=True, routed_scaling_factor=2.446,
                 linear_attn_config=None, kda_gate_rank=None, kda_chunk=64,
                 bias_update_speed=0.001, balance_loss_weight=0.0,
                 initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        linear = dict(linear_attn_config or KIMI_LINEAR_ATTN)
        cfg = dict(
            hidden_size=hidden_size, num_attention_heads=num_attention_heads,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=n_routed_experts,
            num_experts_per_tok=num_experts_per_tok,
            n_shared_experts=n_shared_experts, q_lora_rank=None,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rms_norm_eps=rms_norm_eps, rope_theta=10000.0, rope=False,
            norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor,
            bias_update_speed=bias_update_speed,
            balance_loss_weight=balance_loss_weight,
            held_experts=None if held_experts is None else tuple(held_experts),
            held_rows_factor=held_rows_factor,
            kda=dict(num_heads=linear["num_heads"],
                     head_dim=linear["head_dim"],
                     short_conv_kernel_size=linear["short_conv_kernel_size"],
                     gate_rank=kda_gate_rank, chunk=kda_chunk))
        self.layer_types = kimi_layer_types(linear, num_hidden_layers)
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            JoyAIDecoderLayer(cfg, dense=i < first_k_dense_replace,
                              weight_attr=attr(), mixer=mixer)
            for i, mixer in enumerate(self.layer_types)])
        self.norm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)

    def features(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return self.norm(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


def kimi_layer_types(linear_attn_config, num_hidden_layers):
    """['kda' | 'mla'] for layers 0 .. n - 1 from the config's two
    1-indexed lists; a layer in neither, or in both, is an error."""
    kda = set(linear_attn_config["kda_layers"])
    full = set(linear_attn_config["full_attn_layers"])
    types = []
    for i in range(1, num_hidden_layers + 1):
        if (i in kda) == (i in full):
            raise ValueError(f"layer {i} must be in exactly one of "
                             "kda_layers and full_attn_layers")
        types.append("kda" if i in kda else "mla")
    return types


# ------------------------------------------------------------ Qwen3-Next
class ZeroCenteredRMSNorm(nn.Layer):
    """RMSNorm in float32 whose weight is stored about zero: ``x rsqrt(mean
    x^2 + eps) (1 + w)``, ``w`` from 0 (Qwen3-Next's block, final and QK
    norms; weight decay then pulls the scale to 1, not to 0 — and the
    configurations that train it take it off weight decay all the same). With
    ``zero_centered=False`` the same float32 arithmetic times ``w`` from 1.
    The result takes x's dtype. The weight is named ``<layer>.norm_weight``
    so that an optimizer's ``apply_decay_param_fun`` can tell it."""

    def __init__(self, hidden_size, eps=1e-6, zero_centered=True):
        super().__init__()
        from ..framework.param_attr import ParamAttr

        self.eps, self.zero_centered = float(eps), bool(zero_centered)
        self.weight = self.create_parameter([hidden_size], attr=ParamAttr(
            name=f"{self.full_name()}.norm_weight",
            initializer=nn.initializer.Constant(
                0.0 if zero_centered else 1.0)))

    def forward(self, x):
        from ..core.dispatch import apply_op

        return apply_op("rms_norm_f32", _rms_norm_f32, x, self.weight,
                        eps=self.eps, zero_centered=self.zero_centered)


def _rms_norm_f32(x, w, *, eps, zero_centered):
    import jax
    import jax.numpy as jnp

    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    scale = 1.0 + wf if zero_centered else wf
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + eps) * scale).astype(x.dtype)


# A Gated DeltaNet layer's element-wise stages, as KimiDeltaAttention's: a
# backward pass keeps a stage's bf16 inputs and rebuilds the float32 — the
# convolution stage's in VMEM where its kernels run, the output norm's
# inside a ``jax.checkpoint`` of its own.
def _gdn_split(qkvz, ba, *, key_heads, d_k, d_v, per_key):
    """The two fused projections, laid out a key head at a time as [q d_k |
    k d_k | v per_key x d_v | z per_key x d_v] and [b per_key | a per_key]
    -> (q | k | v over all heads as one [B, T, channels] stream for the
    convolution, z [B, T, H_v x d_v], b and a [B, T, H_v])."""
    import jax.numpy as jnp

    lead = qkvz.shape[:-1]
    x = qkvz.reshape(*lead, key_heads, 2 * d_k + 2 * per_key * d_v)
    cuts = (d_k, 2 * d_k, 2 * d_k + per_key * d_v)
    q, k, v, z = (part.reshape(*lead, -1)
                  for part in jnp.split(x, cuts, axis=-1))
    y = ba.reshape(*lead, key_heads, 2 * per_key)
    return (jnp.concatenate([q, k, v], axis=-1), z,
            y[..., :per_key].reshape(*lead, -1),
            y[..., per_key:].reshape(*lead, -1))


def _gdn_segments(wide, key_heads, d_k):
    """``conv_streams``' segments of ``_gdn_streams``' ONE stream."""
    key = key_heads * d_k
    return ((0, 0, key, d_k ** -0.5), (0, key, key, 1.0),
            (0, 2 * key, wide - 2 * key, None))


def _gdn_streams(mixed, w, *, key_heads, d_k, eps, kernel="ask"):
    """The q | k | v stream through ONE causal depthwise convolution and
    SiLU -> q, k [B, T, H_k * d_k] L2-normalised a head (in float32), q
    scaled by d_k^-0.5, and v [B, T, H_v * d_v]: streams, as
    KimiDeltaAttention's, and the same op — three segments of one
    stream. ``kernel``: ``conv_kernel``'s answer, taken outside the op."""
    from ..ops.linear_attention import conv_streams

    return conv_streams(
        (mixed,), (w,), _gdn_segments(mixed.shape[-1], key_heads, d_k),
        head=d_k, eps=eps, kernel=kernel)


def _gdn_decay(a, a_log, dt_bias):
    """g = -exp(A_log) softplus(a + dt_bias) in float32: [B, T, H_v], ONE
    decay's logarithm a value head and token."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))


def _gdn_beta(b):
    import jax
    import jax.numpy as jnp

    return jax.nn.sigmoid(b.astype(jnp.float32))


def _repeat_heads(x, *, repeats, axis):
    """Each head ``repeats`` times, neighbours: head h serves heads
    h * repeats .. of the wider side."""
    import jax.numpy as jnp

    return x if repeats == 1 else jnp.repeat(x, repeats, axis=axis)


def _repeat_head_lanes(x, *, heads, repeats):
    """``_repeat_heads`` on a stream [.., H * d]: each head's d lanes laid
    down ``repeats`` times, neighbours — slices of the last axis side by
    side, no [.., H, repeats, d] view. Its gradient is stated (a head's is
    the sum of its copies' slices): differentiating the slices gives one
    zero-padded stream a copy."""
    import jax
    import jax.numpy as jnp

    if repeats == 1:
        return x
    d = x.shape[-1] // heads

    def lanes(x, head):
        return x[..., head * d:(head + 1) * d]

    @jax.custom_vjp
    def repeat(x):
        return jnp.concatenate([lanes(x, h) for h in range(heads)
                                for _ in range(repeats)], axis=-1)

    def backward(_, dy):
        return (jnp.concatenate(
            [sum(lanes(dy, h * repeats + j) for j in range(repeats))
             for h in range(heads)], axis=-1),)

    repeat.defvjp(lambda x: (repeat(x), None), backward)
    return repeat(x)


def _gdn_gated_norm(o, z, w, *, heads, eps):
    """``w * RMSNorm_d(o) * silu(z)`` a head, in float32 (w from 1: this
    norm is not zero-centred); [B, T, H * d] -> the same in o's dtype."""
    import jax

    def gated(o, z, w):
        return _gated_head_norm(o, z, w, heads, eps=eps,
                                activation=jax.nn.silu)

    return jax.checkpoint(gated)(o, z, w)


class GatedDeltaNet(nn.Layer):
    """Gated DeltaNet as Qwen3-Next has it (HF ``qwen3_next``; Yang et al.,
    arXiv:2412.06464): ``num_k_heads`` key heads serve ``num_v_heads`` value
    heads (each q / k head ``num_v_heads / num_k_heads`` neighbours). One
    fused projection gives q | k | v | z and one b | a, both laid out a key
    head at a time; q | k | v pass ONE causal depthwise convolution of
    ``conv_kernel_size`` taps and SiLU; q and k are L2-normalised a head;
    ``beta = sigmoid(b)`` and ONE decay a value head and token, ``g =
    -exp(A_log) softplus(a + dt_bias)``; the gated delta rule's state
    recurrence (``ops.linear_attention`` with its ``[B, T, H]`` decay); and
    ``out_proj(w * RMSNorm_d(o) * silu(z))``. The convolution's
    multiply-adds, SiLU, the L2 norms, the decay, beta, the scan's state
    and the output norm are float32 under amp O1; the projections and the
    scan's large products take bf16 operands. Convolution, SiLU and norms
    are one op (``ops.linear_attention.conv_streams``, q, k and v three
    segments of the one stream: one Mosaic call a pass on a TPU at
    lane-wide heads, XLA operations elsewhere). Scopes: ``gdn.proj`` /
    ``.conv`` / ``.gate`` / ``.repeat`` (q and k written once a value head:
    what an index map in the scan's kernels would save) / ``.core`` /
    ``.out``."""

    def __init__(self, hidden_size, num_k_heads=16, num_v_heads=32,
                 head_k_dim=128, head_v_dim=128, conv_kernel_size=4,
                 chunk=64, rms_norm_eps=1e-6, l2_eps=1e-6, weight_attr=None):
        super().__init__()
        if num_v_heads % num_k_heads:
            raise ValueError(f"{num_v_heads} value heads are no multiple of "
                             f"{num_k_heads} key heads")
        self.num_k_heads, self.num_v_heads = num_k_heads, num_v_heads
        self.head_k_dim, self.head_v_dim = head_k_dim, head_v_dim
        self.chunk, self.l2_eps = int(chunk), float(l2_eps)
        key, value = num_k_heads * head_k_dim, num_v_heads * head_v_dim

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.in_proj_qkvz = proj(hidden_size, 2 * key + 2 * value)
        self.in_proj_ba = proj(hidden_size, 2 * num_v_heads)
        self.conv1d = nn.CausalDepthwiseConv1D(2 * key + value,
                                               conv_kernel_size,
                                               activation="silu")
        # A = exp(A_log) ~ U(1, 16) and dt_bias the inverse softplus of
        # dt ~ exp(U(log 1e-3, log 1e-1)), one a value head (the Mamba-2
        # convention, as KimiDeltaAttention's); named for an optimizer's
        # apply_decay_param_fun
        from ..framework.param_attr import ParamAttr

        def named(name, low, high):
            return ParamAttr(name=f"{self.full_name()}.{name}",
                             initializer=nn.initializer.Uniform(low, high))

        self.A_log = self.create_parameter(
            [num_v_heads], attr=named("A_log", 1.0, 16.0))
        self.A_log.set_value(np.log(np.asarray(self.A_log._value)))
        self.dt_bias = self.create_parameter(
            [num_v_heads], attr=named("dt_bias", math.log(1e-3),
                                      math.log(1e-1)))
        dt = np.exp(np.asarray(self.dt_bias._value, np.float64))
        self.dt_bias.set_value(dt + np.log(-np.expm1(-dt)))
        self.norm = ZeroCenteredRMSNorm(head_v_dim, eps=rms_norm_eps,
                                        zero_centered=False)
        self.out_proj = proj(value, hidden_size)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.linear_attention import conv_kernel, gated_delta_rule

        per_key = self.num_v_heads // self.num_k_heads
        with jax.named_scope("gdn.proj"):
            mixed, z, b, a = apply_op(
                "gdn_split", _gdn_split, self.in_proj_qkvz(x),
                self.in_proj_ba(x), key_heads=self.num_k_heads,
                d_k=self.head_k_dim, d_v=self.head_v_dim, per_key=per_key)
        with jax.named_scope("gdn.conv"):
            q, k, v = apply_op(
                "gdn_streams", _gdn_streams, mixed, self.conv1d.weight,
                key_heads=self.num_k_heads, d_k=self.head_k_dim,
                eps=self.l2_eps, kernel=conv_kernel(
                    mixed, self.conv1d.weight, _gdn_segments(
                        mixed.shape[-1], self.num_k_heads, self.head_k_dim),
                    self.head_k_dim))
        with jax.named_scope("gdn.gate"):
            g = apply_op("gdn_decay", _gdn_decay, a, self.A_log,
                         self.dt_bias)
            beta = apply_op("gdn_beta", _gdn_beta, b)
        with jax.named_scope("gdn.repeat"):
            q, k = (apply_op("repeat_head_lanes", _repeat_head_lanes, t,
                             heads=self.num_k_heads, repeats=per_key)
                    for t in (q, k))
        with jax.named_scope("gdn.core"):
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope("gdn.out"):
            return self.out_proj(apply_op(
                "gdn_gated_norm", _gdn_gated_norm, o, z, self.norm.weight,
                heads=self.num_v_heads, eps=self.norm.eps))


def _gqa_heads(q, k, v, w_q, w_k, *, heads, kv_heads, d, eps, base,
               rotary_dim, kernel=None):
    """The projected streams -> (query, key, value [B, H, T, d] — key and
    value on their own ``kv_heads`` — and the gate [B, T, heads x d]): the
    query projection is laid out a head at a time as [query d | gate d];
    query and key pass a zero-centred RMSNorm over a head's d features and
    rotate-half RoPE on the first ``rotary_dim`` of them, in float32. That
    stage is one op, ``ops.attention.qk_heads``, which reads a head's query
    out of every second d-wide column block of the stream; the gate's cut
    and v's head split are views and stay here. ``kernel``: ``qk_kernel``'s
    answer, taken outside the op (None: the XLA stage)."""
    from ..ops.attention import qk_heads

    b, t, _ = q.shape
    query, key = qk_heads(
        q, k, w_q, w_k, heads=heads, kv_heads=kv_heads, zero_centered=True,
        eps=eps, rope=True, base=base, rotary_dim=rotary_dim, stride=2,
        kernel=kernel)
    gate = q.reshape(b, t, heads, 2 * d)[..., d:].reshape(b, t, heads * d)
    return (query, key, v.reshape(b, t, kv_heads, d).transpose(0, 2, 1, 3),
            gate)


def _gqa_gated_merge(o, gate):
    """[B, H, T, d] -> [B, T, H x d], times sigmoid(gate)."""
    import jax
    import jax.numpy as jnp

    b, h, t, d = o.shape
    merged = o.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    return (merged.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(o.dtype)


class GatedGQAttention(nn.Layer):
    """Qwen3-Next's full-attention mixer: grouped-query attention whose
    head width is its own (``head_dim``, not hidden / heads), the query
    projection twice as wide and split a head into [query | gate], a
    zero-centred RMSNorm over each head's features of q and of k,
    rotate-half RoPE on the first ``partial_rotary_factor`` of them, causal
    softmax attention at ``head_dim ** -0.5`` with query head h on
    key/value head ``h // (heads / kv_heads)``, the core's output times
    ``sigmoid(gate)``, then ``o_proj``; no bias anywhere. The core goes
    through the dispatching sdpa (the streaming flash kernel at long
    sequences), K and V REPEATED to the query heads first, under the scope
    ``gqa.repeat`` (ROADMAP Speed 13: the kernel takes one head count).
    Scopes: ``gqa.proj`` / ``.repeat`` / ``.core`` / ``.out``. The norms,
    RoPE and the head split under ``gqa.proj`` are ``ops.attention.qk_heads``
    (``forward`` asks ``qk_kernel`` outside the dispatched op), which reads
    a head's query out of every second column block of ``q_proj``'s
    output."""

    def __init__(self, hidden_size, num_heads=16, num_kv_heads=2,
                 head_dim=256, partial_rotary_factor=0.25, rope_theta=1e7,
                 rms_norm_eps=1e-6, weight_attr=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no multiple of "
                             f"{num_kv_heads} key/value heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rope_theta = head_dim, float(rope_theta)
        self.rotary_dim = int(head_dim * partial_rotary_factor)

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.q_proj = proj(hidden_size, 2 * num_heads * head_dim)
        self.k_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.v_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.o_proj = proj(num_heads * head_dim, hidden_size)
        self.q_norm = ZeroCenteredRMSNorm(head_dim, eps=rms_norm_eps)
        self.k_norm = ZeroCenteredRMSNorm(head_dim, eps=rms_norm_eps)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.attention import (
            qk_kernel, scaled_dot_product_attention as _sdpa)

        with jax.named_scope("gqa.proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            q, k, v, gate = apply_op(
                "gqa_heads", _gqa_heads, q, k, v, self.q_norm.weight,
                self.k_norm.weight,
                heads=self.num_heads, kv_heads=self.num_kv_heads,
                d=self.head_dim, eps=self.q_norm.eps, base=self.rope_theta,
                rotary_dim=self.rotary_dim, kernel=qk_kernel(
                    q, self.num_heads, self.num_kv_heads, self.head_dim,
                    self.rotary_dim))
        with jax.named_scope("gqa.repeat"):
            k, v = (apply_op("repeat_heads", _repeat_heads, t,
                             repeats=self.num_heads // self.num_kv_heads,
                             axis=1) for t in (k, v))
        with jax.named_scope("gqa.core"):
            o = _sdpa(q, k, v, is_causal=True, training=self.training)
        with jax.named_scope("gqa.out"):
            return self.o_proj(apply_op("gqa_gated_merge", _gqa_gated_merge,
                                        o, gate))


class Qwen3NextDecoderLayer(nn.Layer):
    """Pre-norm block of Qwen3-Next: a token mixer by layer type — Gated
    DeltaNet (``'linear_attention'``) or gated grouped-query attention
    (``'full_attention'``) — then the expert layer: a softmax router over
    all experts, top-k renormalised, a shared expert behind a sigmoid gate
    of its own, and the held range of the routed experts. Both norms are
    zero-centred and float32."""

    def __init__(self, cfg, mixer, weight_attr=None):
        super().__init__()
        from ..incubate.moe import MoELayer

        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.input_layernorm = ZeroCenteredRMSNorm(hidden, eps=eps)
        if mixer == "linear_attention":
            self.linear_attn = GatedDeltaNet(
                hidden, rms_norm_eps=eps, weight_attr=weight_attr,
                **cfg["gdn"])
        else:
            self.self_attn = GatedGQAttention(
                hidden, rms_norm_eps=eps, weight_attr=weight_attr,
                **cfg["gqa"])
        self.mixer = mixer
        self.post_attention_layernorm = ZeroCenteredRMSNorm(hidden, eps=eps)
        self.mlp = MoELayer(
            hidden, cfg["moe_intermediate_size"], cfg["num_experts"],
            top_k=cfg["num_experts_per_tok"], activation="swiglu",
            gate_bias=False, norm_topk_prob=cfg["norm_topk_prob"],
            shared_width=cfg["shared_expert_intermediate_size"],
            shared_gate=True, held=cfg["held_experts"],
            held_rows_factor=cfg["held_rows_factor"],
            aux_weight=cfg["router_aux_loss_coef"], weight_attr=weight_attr)

    def forward(self, x):
        mix = (self.linear_attn if self.mixer == "linear_attention"
               else self.self_attn)
        x = x + mix(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class Qwen3NextModel(_BlockwiseModel):
    """Qwen3-Next-80B-A3B (HF ``qwen3_next``): pre-norm blocks whose mixer
    goes by layer type — gated grouped-query attention on every
    ``full_attention_interval``-th layer, Gated DeltaNet on the others —
    each followed by the expert layer (``decoder_sparse_step`` 1, no dense
    layer); a zero-centred final norm and an untied head; no MTP module
    (the published checkpoint's is dropped by the HF model). Defaults are
    the published sizes.

    ``held_experts=(first, count)`` gives every expert layer this chip's
    range of the routed experts; ``use_recompute`` runs each block under
    ``fleet.utils.recompute`` in a traced step. ``forward`` gives the
    logits; a training loss takes ``features`` and ``lm_head.weight`` to
    ``F.linear_cross_entropy``."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=1e7,
                 full_attention_interval=4, linear_num_key_heads=16,
                 linear_num_value_heads=32, linear_key_head_dim=128,
                 linear_value_head_dim=128, linear_conv_kernel_dim=4,
                 moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_experts=512,
                 num_experts_per_tok=10, norm_topk_prob=True,
                 router_aux_loss_coef=0.001, rms_norm_eps=1e-6,
                 gdn_chunk=64, initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        cfg = dict(
            hidden_size=hidden_size, rms_norm_eps=rms_norm_eps,
            moe_intermediate_size=moe_intermediate_size,
            shared_expert_intermediate_size=shared_expert_intermediate_size,
            num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
            norm_topk_prob=norm_topk_prob,
            router_aux_loss_coef=router_aux_loss_coef,
            held_experts=None if held_experts is None else tuple(held_experts),
            held_rows_factor=held_rows_factor,
            gdn=dict(num_k_heads=linear_num_key_heads,
                     num_v_heads=linear_num_value_heads,
                     head_k_dim=linear_key_head_dim,
                     head_v_dim=linear_value_head_dim,
                     conv_kernel_size=linear_conv_kernel_dim,
                     chunk=gdn_chunk),
            gqa=dict(num_heads=num_attention_heads,
                     num_kv_heads=num_key_value_heads, head_dim=head_dim,
                     partial_rotary_factor=partial_rotary_factor,
                     rope_theta=rope_theta))
        self.layer_types = qwen3_next_layer_types(num_hidden_layers,
                                                  full_attention_interval)
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            Qwen3NextDecoderLayer(cfg, mixer, weight_attr=attr())
            for mixer in self.layer_types])
        self.norm = ZeroCenteredRMSNorm(hidden_size, eps=rms_norm_eps)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)

    def features(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return self.norm(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


def qwen3_next_layer_types(num_hidden_layers, full_attention_interval=4):
    """Layer i (from 0) is ``full_attention`` where (i + 1) is a multiple of
    the interval, else ``linear_attention`` (HF ``layer_types``' default)."""
    return ["full_attention" if (i + 1) % full_attention_interval == 0
            else "linear_attention" for i in range(num_hidden_layers)]


# ------------------------------------------------------------ Trinity (AFMoE)
def _afmoe_heads(q, k, v, w_q, w_k, *, heads, kv_heads, d, eps, base, rope,
                 kernel=None):
    """The projected streams -> (query [B, H, T, d], key and value on their
    own ``kv_heads``): query and key pass an RMSNorm over a head's d
    features (weight from 1) and, where ``rope``, rotate-half RoPE over all
    of them, in float32; a layer without positions rotates nothing. That
    stage is one op, ``ops.attention.qk_heads`` (one Mosaic call a pass on
    the streams where ``qk_path`` says ``kernel`` — heads of whole lane
    groups on a TPU —, XLA operations under a ``jax.checkpoint`` elsewhere:
    heads of 64); v's head split needs no arithmetic and stays here.
    ``kernel``: ``qk_kernel``'s answer, taken outside the op (None: the XLA
    stage)."""
    from ..ops.attention import qk_heads

    b, t, _ = q.shape
    query, key = qk_heads(
        q, k, w_q, w_k, heads=heads, kv_heads=kv_heads, zero_centered=False,
        eps=eps, rope=rope, base=base, kernel=kernel)
    return query, key, v.reshape(b, t, kv_heads, d).transpose(0, 2, 1, 3)


class AfmoeAttention(nn.Layer):
    """Trinity's (HF ``afmoe``) attention by layer type. Both types: grouped
    queries at a head width of its own (``head_dim``), an RMSNorm over each
    head's features of q and of k, causal softmax at ``head_dim ** -0.5``
    with query head h on key/value head ``h // (heads / kv_heads)``, the
    core's output times ``sigmoid(gate_proj(x))`` — a matrix of its own —
    then ``o_proj``; no bias. A ``sliding_attention`` layer rotates q and k
    (rotate-half RoPE over all features) and sees the ``sliding_window``
    keys up to the query's own position; a ``full_attention`` layer sees
    every earlier key and rotates NOTHING (no positions). The core goes
    through the dispatching sdpa with the layer's ``window`` (the banded
    streaming kernel at long sequences), K and V REPEATED to the query
    heads first (ROADMAP Speed 13). Scopes, so that a trace tells the two
    types apart: ``swa.`` (sliding) or ``gattn.`` (full) + ``proj`` (q, k,
    v and the gate) / ``qk`` (norms, RoPE, the head split) / ``repeat`` /
    ``core`` / ``out`` (gate, merge, ``o_proj``). The ``qk`` stage is
    ``ops.attention.qk_heads``: at this head width one Mosaic call a pass on
    the projected streams where a program may hold kernels; ``forward`` asks
    ``qk_kernel`` (``ops.attention.qk_path`` over ``ops.placement``) outside
    the dispatched op, and ``paddle_tpu_qk_heads_total{path}`` counts the
    answer."""

    def __init__(self, hidden_size, layer_type, num_heads=32, num_kv_heads=4,
                 head_dim=128, sliding_window=2048, rope_theta=10000.0,
                 rms_norm_eps=1e-5, weight_attr=None):
        super().__init__()
        if layer_type not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_type {layer_type!r} is neither "
                             "'sliding_attention' nor 'full_attention'")
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no multiple of "
                             f"{num_kv_heads} key/value heads")
        self.sliding = layer_type == "sliding_attention"
        self.window = int(sliding_window) if self.sliding else None
        self.scope = "swa" if self.sliding else "gattn"
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rope_theta = head_dim, float(rope_theta)

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.q_proj = proj(hidden_size, num_heads * head_dim)
        self.k_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.v_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.gate_proj = proj(hidden_size, num_heads * head_dim)
        self.o_proj = proj(num_heads * head_dim, hidden_size)
        self.q_norm = ZeroCenteredRMSNorm(head_dim, eps=rms_norm_eps,
                                          zero_centered=False)
        self.k_norm = ZeroCenteredRMSNorm(head_dim, eps=rms_norm_eps,
                                          zero_centered=False)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.attention import (
            qk_kernel, scaled_dot_product_attention as _sdpa)

        scope = self.scope
        with jax.named_scope(scope + ".proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            gate = self.gate_proj(x)
        with jax.named_scope(scope + ".qk"):
            q, k, v = apply_op(
                "afmoe_heads", _afmoe_heads, q, k, v, self.q_norm.weight,
                self.k_norm.weight, heads=self.num_heads,
                kv_heads=self.num_kv_heads, d=self.head_dim,
                eps=self.q_norm.eps, base=self.rope_theta,
                rope=self.sliding, kernel=qk_kernel(
                    q, self.num_heads, self.num_kv_heads, self.head_dim,
                    self.head_dim if self.sliding else None))
        with jax.named_scope(scope + ".repeat"):
            k, v = (apply_op("repeat_heads", _repeat_heads, t,
                             repeats=self.num_heads // self.num_kv_heads,
                             axis=1) for t in (k, v))
        with jax.named_scope(scope + ".core"):
            o = _sdpa(q, k, v, is_causal=True, training=self.training,
                      window=self.window)
        with jax.named_scope(scope + ".out"):
            return self.o_proj(apply_op("gqa_gated_merge", _gqa_gated_merge,
                                        o, gate))


class AfmoeDecoderLayer(nn.Layer):
    """Trinity's block, a norm before AND after each sublayer (four
    RMSNorms, weight from 1, float32): ``a = h + post_attention_layernorm(
    Attn(input_layernorm(h)))``, ``h' = a + post_mlp_layernorm(MLP(
    pre_mlp_layernorm(a)))``. The MLP is a dense SwiGLU (the leading
    layers) or the expert layer: a sigmoid router with a selection bias
    moved without an auxiliary loss, the chosen scores renormalised times
    ``route_scale``, a shared expert, and the held range of the routed
    experts."""

    def __init__(self, cfg, layer_type, dense, weight_attr=None):
        super().__init__()
        from ..incubate.moe import MoELayer

        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]

        def norm():
            return ZeroCenteredRMSNorm(hidden, eps=eps, zero_centered=False)

        self.input_layernorm = norm()
        self.self_attn = AfmoeAttention(
            hidden, layer_type, rms_norm_eps=eps, weight_attr=weight_attr,
            **cfg["attention"])
        self.post_attention_layernorm = norm()
        self.pre_mlp_layernorm = norm()
        if dense:
            self.mlp = LlamaMLP(hidden, cfg["intermediate_size"],
                                weight_attr)
        else:
            self.mlp = MoELayer(
                hidden, cfg["moe_intermediate_size"], cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"], activation="swiglu",
                gate_bias=False, norm_topk_prob=cfg["route_norm"],
                scoring="sigmoid", select_bias=True,
                bias_update_speed=cfg["load_balance_coeff"],
                routed_scale=cfg["route_scale"],
                shared_width=cfg["num_shared_experts"]
                * cfg["moe_intermediate_size"],
                held=cfg["held_experts"],
                held_rows_factor=cfg["held_rows_factor"], aux_weight=0.0,
                weight_attr=weight_attr)
        self.post_mlp_layernorm = norm()

    def forward(self, x):
        x = x + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(x)))
        return x + self.post_mlp_layernorm(
            self.mlp(self.pre_mlp_layernorm(x)))


class AfmoeModel(_BlockwiseModel):
    """Trinity-Mini (arcee-ai, HF ``afmoe``): sandwich-norm blocks whose
    attention goes by ``layer_types`` — a sliding window of
    ``sliding_window`` keys with RoPE, or full attention without positions
    on every ``global_attn_every_n_layers``-th layer —, the first
    ``num_dense_layers`` with a dense SwiGLU and the others with the expert
    layer; the embedding times ``sqrt(hidden_size)`` (``mup_enabled``); a
    final norm and an untied head. Defaults are the published sizes.

    ``held_experts=(first, count)`` gives every expert layer this chip's
    range of the routed experts; ``use_recompute`` runs each block under
    ``fleet.utils.recompute`` in a traced step. ``forward`` gives the
    logits; a training loss takes ``features`` and ``lm_head.weight`` to
    ``F.linear_cross_entropy``."""

    def __init__(self, vocab_size=200192, hidden_size=2048,
                 num_hidden_layers=32, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, intermediate_size=6144,
                 moe_intermediate_size=1024, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=1,
                 num_dense_layers=2, layer_types=None,
                 global_attn_every_n_layers=4, sliding_window=2048,
                 rope_theta=10000.0, rms_norm_eps=1e-5, route_norm=True,
                 route_scale=2.826, load_balance_coeff=0.001,
                 mup_enabled=True, initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        cfg = dict(
            hidden_size=hidden_size, rms_norm_eps=rms_norm_eps,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
            num_shared_experts=num_shared_experts, route_norm=route_norm,
            route_scale=route_scale, load_balance_coeff=load_balance_coeff,
            held_experts=None if held_experts is None else tuple(held_experts),
            held_rows_factor=held_rows_factor,
            attention=dict(num_heads=num_attention_heads,
                           num_kv_heads=num_key_value_heads,
                           head_dim=head_dim, sliding_window=sliding_window,
                           rope_theta=rope_theta))
        self.layer_types = list(layer_types or afmoe_layer_types(
            num_hidden_layers, global_attn_every_n_layers))
        if len(self.layer_types) != num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer types for "
                             f"{num_hidden_layers} layers")
        self.embed_scale = math.sqrt(hidden_size) if mup_enabled else 1.0
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            AfmoeDecoderLayer(cfg, layer_type, dense=i < num_dense_layers,
                              weight_attr=attr())
            for i, layer_type in enumerate(self.layer_types)])
        self.norm = ZeroCenteredRMSNorm(hidden_size, eps=rms_norm_eps,
                                        zero_centered=False)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)

    def features(self, input_ids):
        x = self.embed_tokens(input_ids) * self.embed_scale
        for layer in self.layers:
            x = self._block(layer, x)
        return self.norm(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


def afmoe_layer_types(num_hidden_layers, global_attn_every_n_layers=4):
    """Layer i (from 0) is ``full_attention`` where (i + 1) is a multiple of
    the interval, else ``sliding_attention`` (the published ``layer_types``)."""
    return ["full_attention" if (i + 1) % global_attn_every_n_layers == 0
            else "sliding_attention" for i in range(num_hidden_layers)]


# ------------------------------------------------------------ LFM2 (lfm2_moe)
class Lfm2ShortConv(nn.Layer):
    """LFM2's token mixer in its ``conv`` layers, the doubly gated short
    convolution: ``[B | C | u] = in_proj(x)`` (hidden -> 3 x hidden, split
    in that order), ``y = out_proj(C * conv(B * u))`` with ``conv`` a causal
    depthwise convolution of ``kernel_size`` (``conv_L_cache`` = 3) taps,
    one filter a channel; no bias, no activation, nothing recurrent: the
    mixer reaches ``kernel_size`` tokens, and a row's first tokens see
    zeros, never another row. The stage between the projections is
    ``ops.linear_attention.gated_short_conv``, which takes the decision
    itself (``shortconv_path``, counted in
    ``paddle_tpu_shortconv_total{path}``): one Mosaic kernel a pass on the
    ``[B | C | u]`` stream where the program may hold it and the channels
    fill lane groups, else XLA operations under a ``jax.checkpoint`` of
    their own. Scopes: ``shortconv.in_proj`` / ``.stage`` / ``.out_proj``."""

    def __init__(self, hidden_size, kernel_size=3, weight_attr=None):
        super().__init__()

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.in_proj = proj(hidden_size, 3 * hidden_size)
        self.conv = nn.CausalDepthwiseConv1D(hidden_size, kernel_size)
        self.out_proj = proj(hidden_size, hidden_size)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops import linear_attention

        with jax.named_scope("shortconv.in_proj"):
            bcu = self.in_proj(x)
        with jax.named_scope("shortconv.stage"):
            y = apply_op("gated_short_conv",
                         linear_attention.gated_short_conv, bcu,
                         self.conv.weight)
        with jax.named_scope("shortconv.out_proj"):
            return self.out_proj(y)


class Lfm2Attention(nn.Layer):
    """LFM2's ``full_attention`` mixer: grouped queries (``num_heads`` on
    ``num_kv_heads`` heads of ``head_dim`` = hidden / heads = 64), an
    RMSNorm over each head's features of q and of k (``q_layernorm``,
    ``k_layernorm``, weight from 1), rotate-half RoPE over all of them,
    causal softmax at ``head_dim ** -0.5`` with query head h on key/value
    head ``h // (heads / kv_heads)``, then ``out_proj``; no bias, no gate.
    The core goes through the dispatching sdpa (the streaming flash kernel
    at long sequences), K and V REPEATED to the query heads first (ROADMAP
    Speed 13). Scopes: ``lfm2attn.proj`` (q, k, v) / ``.qk`` (norms, RoPE,
    the head split) / ``.repeat`` / ``.core`` / ``.out`` (merge,
    ``out_proj``). The ``qk`` stage is ``ops.attention.qk_heads`` as
    Trinity's; ``forward`` asks ``qk_kernel`` the same way, and at heads of
    64 — half a lane group — ``qk_path`` answers ``xla`` on every platform:
    the stage runs as XLA operations under its own ``jax.checkpoint``
    (ROADMAP Speed 11b: two heads a lane group, stage and core together)."""

    def __init__(self, hidden_size, num_heads=32, num_kv_heads=8,
                 rope_theta=1e6, rms_norm_eps=1e-5, weight_attr=None):
        super().__init__()
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise ValueError(
                f"{num_heads} query heads on {num_kv_heads} key/value heads "
                f"do not divide a hidden size of {hidden_size}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.rope_theta = float(rope_theta)

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.q_proj = proj(hidden_size, hidden_size)
        self.k_proj = proj(hidden_size, num_kv_heads * self.head_dim)
        self.v_proj = proj(hidden_size, num_kv_heads * self.head_dim)
        self.out_proj = proj(hidden_size, hidden_size)
        self.q_layernorm = ZeroCenteredRMSNorm(
            self.head_dim, eps=rms_norm_eps, zero_centered=False)
        self.k_layernorm = ZeroCenteredRMSNorm(
            self.head_dim, eps=rms_norm_eps, zero_centered=False)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.attention import (
            qk_kernel, scaled_dot_product_attention as _sdpa)

        with jax.named_scope("lfm2attn.proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with jax.named_scope("lfm2attn.qk"):
            q, k, v = apply_op(
                "afmoe_heads", _afmoe_heads, q, k, v,
                self.q_layernorm.weight, self.k_layernorm.weight,
                heads=self.num_heads, kv_heads=self.num_kv_heads,
                d=self.head_dim, eps=self.q_layernorm.eps,
                base=self.rope_theta, rope=True, kernel=qk_kernel(
                    q, self.num_heads, self.num_kv_heads, self.head_dim,
                    self.head_dim))
        with jax.named_scope("lfm2attn.repeat"):
            k, v = (apply_op("repeat_heads", _repeat_heads, t,
                             repeats=self.num_heads // self.num_kv_heads,
                             axis=1) for t in (k, v))
        with jax.named_scope("lfm2attn.core"):
            o = _sdpa(q, k, v, is_causal=True, training=self.training)
        with jax.named_scope("lfm2attn.out"):
            return self.out_proj(apply_op("merge_heads", _merge_heads, o))


class Lfm2DecoderLayer(nn.Layer):
    """LFM2's pre-norm block: ``a = h + Op(operator_norm(h))``, ``h' = a +
    FFN(ffn_norm(a))``. The operator goes by the layer's type — the gated
    short convolution (``conv``) or grouped-query attention
    (``full_attention``) —, the FFN by its index: a dense SwiGLU in the
    leading layers, else the expert layer: a sigmoid router over all
    experts, the choice by score + ``expert_bias`` (moved without an
    auxiliary loss), the chosen scores over their sum + 1e-6 times
    ``routed_scaling_factor``, no shared expert, and the held range of the
    routed experts."""

    def __init__(self, cfg, layer_type, dense, weight_attr=None):
        super().__init__()
        from ..incubate.moe import MoELayer

        if layer_type not in ("conv", "full_attention"):
            raise ValueError(f"layer_type {layer_type!r} is neither 'conv' "
                             "nor 'full_attention'")
        hidden, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.operator_norm = ZeroCenteredRMSNorm(hidden, eps=eps,
                                                 zero_centered=False)
        self.is_attention = layer_type == "full_attention"
        if self.is_attention:
            self.self_attn = Lfm2Attention(
                hidden, rms_norm_eps=eps, weight_attr=weight_attr,
                **cfg["attention"])
        else:
            self.conv = Lfm2ShortConv(hidden, cfg["conv_L_cache"],
                                      weight_attr)
        self.ffn_norm = ZeroCenteredRMSNorm(hidden, eps=eps,
                                            zero_centered=False)
        if dense:
            self.feed_forward = LlamaMLP(hidden, cfg["intermediate_size"],
                                         weight_attr)
        else:
            self.feed_forward = MoELayer(
                hidden, cfg["moe_intermediate_size"], cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"], activation="swiglu",
                gate_bias=False, norm_topk_prob=cfg["norm_topk_prob"],
                scoring="sigmoid", select_bias=cfg["use_expert_bias"],
                bias_update_speed=cfg["bias_update_speed"],
                routed_scale=cfg["routed_scaling_factor"], renorm_eps=1e-6,
                held=cfg["held_experts"],
                held_rows_factor=cfg["held_rows_factor"], aux_weight=0.0,
                weight_attr=weight_attr)

    def forward(self, x):
        operator = self.self_attn if self.is_attention else self.conv
        x = x + operator(self.operator_norm(x))
        return x + self.feed_forward(self.ffn_norm(x))


class _TiedHead(nn.Layer):
    """The head that IS the embedding: ``logits = x E^T`` on the
    embedding's own [vocab, hidden] parameter (one entry in the state, under
    the embedding's name). ``weight`` gives it as an ``nn.Linear``'s
    [hidden, vocab], what ``F.linear_cross_entropy`` takes."""

    def __init__(self, embedding):
        super().__init__()
        self.embedding_weight = embedding.weight

    @property
    def weight(self):
        from .. import tensor as pt

        return pt.transpose(self.embedding_weight, [1, 0])

    def forward(self, x):
        from .. import tensor as pt

        return pt.matmul(x, self.embedding_weight, transpose_y=True)


class Lfm2Model(_BlockwiseModel):
    """LFM2-8B-A1B (LiquidAI, HF ``lfm2_moe``): pre-norm blocks whose
    operator goes by ``layer_types`` — the doubly gated short convolution
    in three layers of every four, grouped-query attention at heads of
    hidden / heads in the fourth —, the first ``num_dense_layers`` with a
    dense SwiGLU and the others with the expert layer; a final norm
    (``embedding_norm``) and a head TIED to the embedding. Defaults are the
    published sizes.

    ``held_experts=(first, count)`` gives every expert layer this chip's
    range of the routed experts; ``use_recompute`` runs each block under
    ``fleet.utils.recompute`` in a traced step. ``forward`` gives the
    logits; a training loss takes ``features`` and ``lm_head.weight`` to
    ``F.linear_cross_entropy``."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 num_hidden_layers=24, num_attention_heads=32,
                 num_key_value_heads=8, intermediate_size=7168,
                 moe_intermediate_size=1792, num_experts=32,
                 num_experts_per_tok=4, num_dense_layers=2, layer_types=None,
                 conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0, bias_update_speed=0.001,
                 initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        cfg = dict(
            hidden_size=hidden_size, norm_eps=norm_eps,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
            norm_topk_prob=norm_topk_prob, use_expert_bias=use_expert_bias,
            routed_scaling_factor=routed_scaling_factor,
            bias_update_speed=bias_update_speed, conv_L_cache=conv_L_cache,
            held_experts=None if held_experts is None else tuple(held_experts),
            held_rows_factor=held_rows_factor,
            attention=dict(num_heads=num_attention_heads,
                           num_kv_heads=num_key_value_heads,
                           rope_theta=rope_theta))
        self.layer_types = list(layer_types or lfm2_layer_types(
            num_hidden_layers))
        if len(self.layer_types) != num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer types for "
                             f"{num_hidden_layers} layers")
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            Lfm2DecoderLayer(cfg, layer_type, dense=i < num_dense_layers,
                             weight_attr=attr())
            for i, layer_type in enumerate(self.layer_types)])
        self.embedding_norm = ZeroCenteredRMSNorm(hidden_size, eps=norm_eps,
                                                  zero_centered=False)
        self.lm_head = _TiedHead(self.embed_tokens)

    def features(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return self.embedding_norm(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


#: LFM2-8B-A1B's published ``layer_types``: two convolution layers, then
#: attention before every three of them, the last two periods one shorter
_LFM2_ATTENTION_LAYERS = (2, 6, 10, 14, 18, 21)


def lfm2_layer_types(num_hidden_layers):
    """Layer i (from 0) of the published 24 is ``full_attention`` at 2, 6,
    10, 14, 18 and 21 and ``conv`` elsewhere; another depth takes the
    pattern's start."""
    return ["full_attention" if i in _LFM2_ATTENTION_LAYERS else "conv"
            for i in range(num_hidden_layers)]


# A Mamba-2 layer's element-wise stages, as the linear-attention layers':
# float32 arrays as large as a stream, each under a ``jax.checkpoint`` of
# its own (the convolution stage's is ``conv_streams``'), so that a backward
# pass keeps the stage's bf16 inputs and rebuilds the float32.
def _mamba_split(zxbcdt, *, inner, conv):
    """``in_proj``'s output, split [z inner | xBC conv | dt heads] in that
    order."""
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
            zxbcdt[..., inner + conv:])


def _mamba_segments(inner, state):
    """``conv_streams``' segments of the ONE biased x | B | C stream: none
    of them normed."""
    return ((0, 0, inner, None), (0, inner, state, None),
            (0, inner + state, state, None))


def _mamba_streams(xbc, w, bias, *, inner, state, head, kernel="ask"):
    """The x | B | C stream through ONE causal depthwise convolution, its
    bias and SiLU -> x [B, T, inner], B and C [B, T, groups x d_state]:
    ``conv_streams`` on three segments that take no norm. ``kernel``:
    ``conv_kernel``'s answer, taken outside the op."""
    from ..ops.linear_attention import conv_streams

    return conv_streams((xbc,), (w,), _mamba_segments(inner, state),
                        head=head, eps=0.0, kernel=kernel,
                        biases=None if bias is None else (bias,))


def _mamba_step(dt, dt_bias, a_log):
    """(the step a head and token, ``softplus(dt + dt_bias)`` — no clamp:
    ``time_step_limit`` is (0, inf) —, the decay's rate a head, ``A =
    -exp(A_log)``), in float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
            -jnp.exp(a_log.astype(f32)))


def _mamba_gated_norm(y, z, w, *, eps, groups=1):
    """``w * RMSNorm(y * silu(z))``: THE GATE FIRST, then one mean square
    over ALL the features of each of ``groups`` groups (Granite's one
    group: all of ``inner``, not a head's; Nemotron-H's 8: ``inner / 8``
    each), in float32 (w from 1); [B, T, inner] -> the same in y's dtype.
    (``_gdn_gated_norm`` / ``_kda_gated_norm`` norm a head and gate
    after.)"""
    import jax
    import jax.numpy as jnp

    def gated(y, z, w):
        f32 = jnp.float32
        g = y.astype(f32) * jax.nn.silu(z.astype(f32))
        if groups > 1:
            g = g.reshape(*g.shape[:-1], groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return (g.reshape(y.shape) * w.astype(f32)).astype(y.dtype)

    return jax.checkpoint(gated)(y, z, w)


class Mamba2Mixer(nn.Layer):
    """Mamba-2's selective state-space mixer as Granite-4.0-H has it (HF
    ``granitemoehybrid`` / ``mamba2``; Dao & Gu, arXiv:2405.21060): ``[z |
    xBC | dt] = in_proj(x)`` (hidden -> inner + (inner + 2 groups x d_state)
    + heads, split in that order, no bias); ``xBC`` through ONE causal
    depthwise convolution of ``d_conv`` taps WITH A BIAS a channel, then
    SiLU, and split ``[x | B | C]`` (``ops.linear_attention.conv_streams``
    on three un-normed segments: the Mosaic kernels or the XLA stage as
    ``conv_path`` says, ``paddle_tpu_conv_streams_total{path}``) — x is
    ``num_heads`` heads of ``head_dim``, B and C are a GROUP's, shared by
    ``num_heads / n_groups`` heads; ``dt = softplus(dt + dt_bias)`` and
    ``A = -exp(A_log)`` a head;
    the state-space scan ``S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T``,
    ``y_t = S_t^T C_t + D x_t`` (``ops.linear_attention.ssd_scan``: the
    scalar-decay scan with no delta correction, on the streams as the
    convolution stage leaves them — the Mosaic kernels ``ssd_chunk_fwd`` /
    ``ssd_chunk_bwd`` where ``ssd_path`` lets a program hold them (bf16
    operands, a state of whole lane groups, values that divide the 128
    lanes, a row of one token block: a train step of the published widths
    on a TPU), else the chunked XLA scan, ``chunk`` and ``segment`` its
    alone; counted in ``paddle_tpu_ssd_core_total{path}``); then THE GATE
    BEFORE THE NORM,
    ``out_proj(w * RMSNorm_inner(y * silu(z)))`` — one mean square over all
    ``inner`` features. Convolution, bias, SiLU, the step, the scan's decay
    sums, mask and state, and the gated norm are float32 under amp O1; the
    projections and the scan's large products take bf16 operands. ``A_log``,
    ``dt_bias``, ``D`` and the norm's weight are named for an optimizer's
    ``apply_decay_param_fun``. Scopes: ``mamba.in_proj`` / ``.conv`` (taps,
    bias, SiLU, the split) / ``.dt`` (softplus, ``-exp(A_log)``) / ``.core``
    (the scan: ``dt x``, ``dt A``, the ``D`` skip) / ``.norm`` /
    ``.out_proj``.

    With ``n_groups`` > 1 (Nemotron-H: 128 heads in 8 groups) the gated
    norm is A GROUP'S: one mean square over each group's ``inner /
    n_groups`` features. ``held_heads=(first, count)`` builds ONE CHIP'S
    SHARE of a mixer whose heads are divided over chips (tensor parallelism
    without its exchange): whole groups of the ``num_heads`` heads — the
    columns of ``in_proj`` for their z, x, B, C and dt, their taps, ``A_log``,
    ``dt_bias``, ``D`` and norm weights, their rows of ``out_proj`` — and
    nothing else; every stage is a head's or a group's own, so the share
    computes exactly its heads' part, and ``out_proj`` gives a PARTIAL SUM
    over the heads held (what the absent chips would add is left out:
    nothing stands in for them or their all-reduce). The share's columns of
    the whole ``in_proj`` are, in its own order, [z | x | B | C | dt] of its
    heads and groups."""

    #: Mamba-2's own start of the step: dt ~ exp(U(log min, log max)),
    #: floored, behind the softplus
    TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 1e-1, 1e-4

    def __init__(self, hidden_size, num_heads=64, head_dim=64, d_state=128,
                 n_groups=1, d_conv=4, conv_bias=True, chunk=None,
                 segment=None, rms_norm_eps=1e-5, weight_attr=None,
                 held_heads=None):
        super().__init__()
        from ..framework.param_attr import ParamAttr
        from ..ops import linear_attention

        if num_heads % n_groups:
            raise ValueError(f"{num_heads} heads are no multiple of "
                             f"{n_groups} groups")
        self.held_heads = None
        if held_heads is not None:
            per_group = num_heads // n_groups
            first, count = self.held_heads = _held_range(
                held_heads, num_heads,
                lambda first, count: (first % per_group == 0
                                      and count % per_group == 0),
                f"no range of whole groups ({per_group} heads each)")
            # what is built and run here: the share's heads and groups
            num_heads, n_groups = count, count // per_group
        self.num_heads, self.head_dim = num_heads, head_dim
        self.d_state, self.n_groups = d_state, n_groups
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * d_state
        self.chunk = int(chunk or linear_attention.SSD_CHUNK)
        self.segment = int(segment or linear_attention.SSD_SEGMENT)

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.in_proj = proj(hidden_size,
                            self.inner + self.conv_dim + num_heads)
        self.conv1d = nn.CausalDepthwiseConv1D(
            self.conv_dim, d_conv, activation="silu", bias=conv_bias)

        # A = exp(A_log) ~ U(1, 16); dt_bias the inverse softplus of dt ~
        # exp(U(log min, log max)) floored; D = 1: Mamba-2's own start
        def named(name, initializer):
            return ParamAttr(name=f"{self.full_name()}.{name}",
                             initializer=initializer)

        self.A_log = self.create_parameter(
            [num_heads], attr=named("A_log", nn.initializer.Uniform(1.0, 16.0)))
        self.A_log.set_value(np.log(np.asarray(self.A_log._value)))
        self.dt_bias = self.create_parameter(
            [num_heads], attr=named("dt_bias", nn.initializer.Uniform(
                math.log(self.TIME_STEP_MIN), math.log(self.TIME_STEP_MAX))))
        dt = np.maximum(np.exp(np.asarray(self.dt_bias._value, np.float64)),
                        self.TIME_STEP_FLOOR)
        self.dt_bias.set_value(dt + np.log(-np.expm1(-dt)))
        self.D = self.create_parameter(
            [num_heads], attr=named("D", nn.initializer.Constant(1.0)))
        self.norm = ZeroCenteredRMSNorm(self.inner, eps=rms_norm_eps,
                                        zero_centered=False)
        self.out_proj = proj(self.inner, hidden_size)

    def forward(self, x):
        import jax

        y = self.heads_output(x)
        with jax.named_scope("mamba.out_proj"):
            return self.out_proj(y)

    def heads_output(self, x):
        """What ``out_proj`` multiplies: the gated, normed outputs of the
        heads built here [B, T, inner] — a head's own function of x, so a
        share's are the whole mixer's at its own features."""
        import jax

        from ..core.dispatch import apply_op
        from ..ops.linear_attention import conv_kernel, ssd_scan

        state = self.n_groups * self.d_state
        with jax.named_scope("mamba.in_proj"):
            z, xbc, dt = apply_op(
                "mamba_split", _mamba_split, self.in_proj(x),
                inner=self.inner, conv=self.conv_dim)
        with jax.named_scope("mamba.conv"):
            xs, b, c = apply_op(
                "mamba_streams", _mamba_streams, xbc, self.conv1d.weight,
                self.conv1d.bias, inner=self.inner, state=state,
                head=self.head_dim, kernel=conv_kernel(
                    xbc, self.conv1d.weight,
                    _mamba_segments(self.inner, state), self.head_dim))
        with jax.named_scope("mamba.dt"):
            dt, a = apply_op("mamba_step", _mamba_step, dt, self.dt_bias,
                             self.A_log)
        with jax.named_scope("mamba.core"):
            y = ssd_scan(xs, dt, a, b, c, self.D, groups=self.n_groups,
                         chunk=self.chunk, segment=self.segment)
        with jax.named_scope("mamba.norm"):
            # one group: Granite's call, as it was
            groups = ({"groups": self.n_groups} if self.n_groups > 1 else {})
            return apply_op("mamba_gated_norm", _mamba_gated_norm, y, z,
                            self.norm.weight, eps=self.norm.eps, **groups)


def _split_heads(x, *, heads):
    """[B, T, H x d] -> [B, H, T, d]."""
    b, t, _ = x.shape
    return x.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)


class GraniteAttention(nn.Layer):
    """Granite-4.0-H's ``attention`` mixer: plain projections, grouped
    queries (``num_heads`` on ``num_kv_heads`` heads of hidden / heads =
    64), NOTHING ROTATED (``position_embedding_type`` ``nope``), no norm a
    head, causal softmax at ``attention_multiplier`` (1/64 — not ``64 **
    -0.5``) with query head h on key/value head ``h // (heads /
    kv_heads)``, then ``o_proj``; no bias, no gate. The core goes through
    the dispatching sdpa with ``scale=`` (the streaming flash kernel at long
    sequences), K and V REPEATED to the query heads first, as
    ``Lfm2Attention``'s. Scopes: ``gattn64.proj`` (q, k, v and the head
    split) / ``.repeat`` / ``.core`` / ``.out`` (merge, ``o_proj``)."""

    def __init__(self, hidden_size, num_heads=32, num_kv_heads=8,
                 attention_multiplier=0.015625, weight_attr=None):
        super().__init__()
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise ValueError(
                f"{num_heads} query heads on {num_kv_heads} key/value heads "
                f"do not divide a hidden size of {hidden_size}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.attention_multiplier = float(attention_multiplier)

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.q_proj = proj(hidden_size, hidden_size)
        self.k_proj = proj(hidden_size, num_kv_heads * self.head_dim)
        self.v_proj = proj(hidden_size, num_kv_heads * self.head_dim)
        self.o_proj = proj(hidden_size, hidden_size)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.attention import scaled_dot_product_attention as _sdpa

        with jax.named_scope("gattn64.proj"):
            q, k, v = (apply_op("split_heads", _split_heads, proj(x),
                                heads=heads)
                       for proj, heads in ((self.q_proj, self.num_heads),
                                           (self.k_proj, self.num_kv_heads),
                                           (self.v_proj, self.num_kv_heads)))
        with jax.named_scope("gattn64.repeat"):
            k, v = (apply_op("repeat_heads", _repeat_heads, t,
                             repeats=self.num_heads // self.num_kv_heads,
                             axis=1) for t in (k, v))
        with jax.named_scope("gattn64.core"):
            o = _sdpa(q, k, v, is_causal=True, training=self.training,
                      scale=self.attention_multiplier)
        with jax.named_scope("gattn64.out"):
            return self.o_proj(apply_op("merge_heads", _merge_heads, o))


class GraniteHybridDecoderLayer(nn.Layer):
    """Granite-4.0-H's pre-norm block with its residual multiplier ``m``:
    ``a = h + m Mixer(input_layernorm(h))``, ``h' = a + m
    MLP(post_attention_layernorm(a))``. The mixer goes by the layer's type —
    the state-space mixer (``mamba``) or grouped-query attention
    (``attention``) —; the feed-forward part is ONE dense SwiGLU on every
    layer (``num_local_experts`` 0: the model's ``shared_mlp``)."""

    def __init__(self, cfg, layer_type, weight_attr=None):
        super().__init__()
        if layer_type not in ("mamba", "attention"):
            raise ValueError(f"layer_type {layer_type!r} is neither 'mamba' "
                             "nor 'attention'")
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.residual_multiplier = float(cfg["residual_multiplier"])
        self.input_layernorm = ZeroCenteredRMSNorm(hidden, eps=eps,
                                                   zero_centered=False)
        self.is_attention = layer_type == "attention"
        if self.is_attention:
            self.self_attn = GraniteAttention(
                hidden, weight_attr=weight_attr, **cfg["attention"])
        else:
            self.mamba = Mamba2Mixer(hidden, rms_norm_eps=eps,
                                     weight_attr=weight_attr, **cfg["mamba"])
        self.post_attention_layernorm = ZeroCenteredRMSNorm(
            hidden, eps=eps, zero_centered=False)
        self.shared_mlp = LlamaMLP(hidden, cfg["intermediate_size"],
                                   weight_attr)

    def forward(self, x):
        mixer = self.self_attn if self.is_attention else self.mamba
        m = self.residual_multiplier
        x = x + mixer(self.input_layernorm(x)) * m
        return x + self.shared_mlp(self.post_attention_layernorm(x)) * m


class GraniteHybridModel(_BlockwiseModel):
    """Granite-4.0-H (IBM, HF ``granitemoehybrid``; granite-4.0-h-micro's
    sizes are the defaults): ``h0 = embedding_multiplier x E[ids]``; pre-norm
    blocks whose mixer goes by ``layer_types`` — Mamba-2's state-space mixer
    in nine layers of every ten, grouped-query attention without positions
    in the tenth — and whose two residual adds carry ``residual_multiplier``,
    a dense SwiGLU in every one; a final norm; ``logits = (h E^T) /
    logits_scaling`` on a head TIED to the embedding.

    ``use_recompute`` runs each block under ``fleet.utils.recompute`` in a
    traced step. ``features`` gives the final hidden states ALREADY divided
    by ``logits_scaling`` (a power of two at the published 8: the same
    logits, exactly), so that ``forward`` = ``lm_head(features)`` and a
    training loss takes ``features`` and ``lm_head.weight`` to
    ``F.linear_cross_entropy``, which has no scale of its own."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=8, intermediate_size=8192,
                 layer_types=None, mamba_n_heads=64, mamba_d_head=64,
                 mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
                 mamba_conv_bias=True, mamba_chunk=None, mamba_segment=None,
                 attention_multiplier=0.015625, embedding_multiplier=12.0,
                 residual_multiplier=0.22, logits_scaling=8.0,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        cfg = dict(
            hidden_size=hidden_size, rms_norm_eps=rms_norm_eps,
            intermediate_size=intermediate_size,
            residual_multiplier=residual_multiplier,
            attention=dict(num_heads=num_attention_heads,
                           num_kv_heads=num_key_value_heads,
                           attention_multiplier=attention_multiplier),
            mamba=dict(num_heads=mamba_n_heads, head_dim=mamba_d_head,
                       d_state=mamba_d_state, n_groups=mamba_n_groups,
                       d_conv=mamba_d_conv, conv_bias=mamba_conv_bias,
                       chunk=mamba_chunk, segment=mamba_segment))
        self.layer_types = list(layer_types or granite_hybrid_layer_types(
            num_hidden_layers))
        if len(self.layer_types) != num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer types for "
                             f"{num_hidden_layers} layers")
        self.embedding_multiplier = float(embedding_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            GraniteHybridDecoderLayer(cfg, layer_type, weight_attr=attr())
            for layer_type in self.layer_types])
        self.norm = ZeroCenteredRMSNorm(hidden_size, eps=rms_norm_eps,
                                        zero_centered=False)
        self.lm_head = _TiedHead(self.embed_tokens)

    def embed(self, input_ids):
        """The first block's input: ``embedding_multiplier x E[ids]``."""
        return self.embed_tokens(input_ids) * self.embedding_multiplier

    def final(self, x):
        """The last block's output -> what the tied head multiplies: the
        final norm over ``logits_scaling``."""
        return self.norm(x) / self.logits_scaling

    def features(self, input_ids):
        x = self.embed(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return self.final(x)

    def forward(self, input_ids):
        return self.lm_head(self.features(input_ids))


def granite_hybrid_layer_types(num_hidden_layers):
    """granite-4.0-h-micro's published ``layer_types``: ``attention`` at
    layers 5, 15, 25 and 35 (from 0) and ``mamba`` elsewhere — nine to one
    in every ten; another depth takes the pattern's start."""
    return ["attention" if i % 10 == 5 else "mamba"
            for i in range(num_hidden_layers)]


class NemotronAttention(nn.Layer):
    """Nemotron-H's ``*`` mixer: plain projections, grouped queries
    (``num_heads`` heads of ``head_dim`` on ``num_kv_heads``), NOTHING
    ROTATED and no norm a head (the family's attention carries no
    positions), causal softmax of ``q k^T / sqrt(head_dim)`` with query head
    h on key/value head ``h // (heads / kv_heads)``, then ``o_proj``; no
    bias. The core goes through the dispatching sdpa (the streaming flash
    kernel at long sequences), K and V REPEATED to the query heads first.

    ``held_heads=(first, count)`` builds ONE CHIP'S SHARE of the heads:
    those query heads' columns of ``q_proj`` and rows of ``o_proj``, and the
    key/value head(s) they read — whole key/value heads' worth of queries,
    or a part of ONE key/value head's (4 of 32 on 1 of 2) —; ``o_proj``
    gives a PARTIAL SUM over the heads held, as ``Mamba2Mixer``'s share
    does. Scopes: ``nattn.qkv`` (q, k, v, the head split and the repeat) /
    ``.core`` / ``.out`` (merge, ``o_proj``)."""

    def __init__(self, hidden_size, num_heads=32, num_kv_heads=2,
                 head_dim=128, weight_attr=None, held_heads=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads are no multiple of "
                             f"{num_kv_heads} key/value heads")
        self.held_heads = None
        if held_heads is not None:
            per_kv = num_heads // num_kv_heads
            first, count = self.held_heads = _held_range(
                held_heads, num_heads,
                lambda first, count: (
                    (first % per_kv == 0 and count % per_kv == 0)
                    or (per_kv % count == 0 and first % count == 0)),
                f"neither whole key/value heads' queries ({per_kv} each) "
                f"nor a part of one's,")
            # what is built and run here
            num_heads, num_kv_heads = count, max(1, count // per_kv)
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim

        def proj(i, o):
            return nn.Linear(i, o, weight_attr=weight_attr, bias_attr=False)

        self.q_proj = proj(hidden_size, num_heads * head_dim)
        self.k_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.v_proj = proj(hidden_size, num_kv_heads * head_dim)
        self.o_proj = proj(num_heads * head_dim, hidden_size)

    def forward(self, x):
        import jax

        from ..core.dispatch import apply_op
        from ..ops.attention import scaled_dot_product_attention as _sdpa

        with jax.named_scope("nattn.qkv"):
            q, k, v = (apply_op("split_heads", _split_heads, proj(x),
                                heads=heads)
                       for proj, heads in ((self.q_proj, self.num_heads),
                                           (self.k_proj, self.num_kv_heads),
                                           (self.v_proj, self.num_kv_heads)))
            k, v = (apply_op("repeat_heads", _repeat_heads, t,
                             repeats=self.num_heads // self.num_kv_heads,
                             axis=1) for t in (k, v))
        with jax.named_scope("nattn.core"):
            o = _sdpa(q, k, v, is_causal=True, training=self.training)
        with jax.named_scope("nattn.out"):
            return self.o_proj(apply_op("merge_heads", _merge_heads, o))


class NemotronHLayer(nn.Layer):
    """A Nemotron-H layer is ONE sublayer: ``h + Mixer(RMSNorm(h))``, the
    mixer by the layer's letter in ``hybrid_override_pattern`` — ``M``
    Mamba-2's state-space mixer (the gated norm a group's), ``*``
    grouped-query attention without positions, ``E`` the LatentMoE expert
    layer (relu² experts in a ``moe_latent_size``-wide latent space between
    two shared projections; sigmoid router with a selection bias over the
    hidden-wide stream, renormalised top-k times ``routed_scaling_factor``;
    a relu² shared expert on the hidden-wide stream), ``-`` a dense relu²
    MLP. No layer here is mixer + feed-forward."""

    KINDS = {"M": "mamba", "*": "attention", "E": "moe", "-": "mlp"}

    def __init__(self, cfg, kind, weight_attr=None):
        super().__init__()
        from ..incubate.moe import MoELayer

        if kind not in self.KINDS.values():
            raise ValueError(f"layer kind {kind!r} is none of "
                             f"{sorted(self.KINDS.values())}")
        hidden, eps = cfg["hidden_size"], cfg["layer_norm_epsilon"]
        self.kind = kind
        self.norm = RMSNorm(hidden, eps=eps)
        if kind == "mamba":
            self.mixer = Mamba2Mixer(hidden, rms_norm_eps=eps,
                                     weight_attr=weight_attr, **cfg["mamba"])
        elif kind == "attention":
            self.mixer = NemotronAttention(hidden, weight_attr=weight_attr,
                                           **cfg["attention"])
        elif kind == "moe":
            self.mixer = MoELayer(hidden, weight_attr=weight_attr,
                                  **cfg["moe"])
        else:
            self.mixer = Relu2MLP(hidden, cfg["intermediate_size"],
                                  weight_attr)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


def nemotron_layer_types(pattern):
    """``hybrid_override_pattern``'s letters as layer kinds: ``M`` ->
    ``mamba``, ``*`` -> ``attention``, ``E`` -> ``moe``, ``-`` -> ``mlp``."""
    try:
        return [NemotronHLayer.KINDS[c] for c in pattern]
    except KeyError as e:
        raise ValueError(f"{e.args[0]!r} in {pattern!r} is none of "
                         f"{sorted(NemotronHLayer.KINDS)}") from None


#: NVIDIA-Nemotron-3-Super-120B-A12B's 88 layers: 40 M, 40 E, 8 attention
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


class NemotronHModel(_MTPDecoder):
    """Nemotron-H / Nemotron 3 (NVIDIA, HF ``nemotron_h``;
    NVIDIA-Nemotron-3-Super-120B-A12B's sizes are the defaults): ``h0 =
    E[ids]``; layers that are ONE sublayer each, ``h + Mixer(RMSNorm(h))``
    by ``hybrid_override_pattern`` (``NemotronHLayer``); a final norm; an
    untied head; and ``num_nextn_predict_layers`` multi-token-prediction
    modules that share the embedding and the head, each wrapping the layers
    of ``mtp_hybrid_override_pattern`` (``*E``: an attention layer, then an
    expert layer).

    ``held_experts``, ``held_mamba_heads`` and ``held_attention_heads`` (each
    ``(first, count)``) give every layer of its kind this chip's share of a
    deployment that divides each layer over chips; each layer computes its
    own experts' or heads' part and that partial sum goes on
    (``MoELayer``, ``Mamba2Mixer``, ``NemotronAttention``).
    ``use_recompute`` runs each layer under ``fleet.utils.recompute`` in a
    traced step. ``forward`` gives the main logits; a training loss takes
    ``training_features`` and ``lm_head.weight`` to ``mtp_lm_loss``."""

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 hybrid_override_pattern=NEMOTRON_3_SUPER_PATTERN,
                 mtp_hybrid_override_pattern="*E",
                 num_nextn_predict_layers=1, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, mamba_num_heads=128,
                 mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                 conv_kernel=4, use_conv_bias=True, mamba_chunk=None,
                 mamba_segment=None, intermediate_size=2688,
                 moe_intermediate_size=2688, moe_latent_size=1024,
                 moe_shared_expert_intermediate_size=5376,
                 n_routed_experts=512, num_experts_per_tok=22,
                 norm_topk_prob=True, routed_scaling_factor=5.0,
                 bias_update_speed=0.001, layer_norm_epsilon=1e-5,
                 initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, held_mamba_heads=None,
                 held_attention_heads=None, use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        def held(share):
            return None if share is None else tuple(share)

        cfg = dict(
            hidden_size=hidden_size, layer_norm_epsilon=layer_norm_epsilon,
            intermediate_size=intermediate_size,
            mamba=dict(num_heads=mamba_num_heads, head_dim=mamba_head_dim,
                       d_state=ssm_state_size, n_groups=n_groups,
                       d_conv=conv_kernel, conv_bias=use_conv_bias,
                       chunk=mamba_chunk, segment=mamba_segment,
                       held_heads=held(held_mamba_heads)),
            attention=dict(num_heads=num_attention_heads,
                           num_kv_heads=num_key_value_heads,
                           head_dim=head_dim,
                           held_heads=held(held_attention_heads)),
            moe=dict(ffn_hidden=moe_intermediate_size,
                     num_experts=n_routed_experts, top_k=num_experts_per_tok,
                     activation="relu2", latent_size=moe_latent_size,
                     gate_bias=False, norm_topk_prob=norm_topk_prob,
                     scoring="sigmoid", select_bias=True,
                     bias_update_speed=bias_update_speed,
                     routed_scale=routed_scaling_factor,
                     shared_width=moe_shared_expert_intermediate_size,
                     held=held(held_experts),
                     held_rows_factor=held_rows_factor, aux_weight=0.0))
        self.layer_types = nemotron_layer_types(hybrid_override_pattern)
        mtp_types = nemotron_layer_types(mtp_hybrid_override_pattern)
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            NemotronHLayer(cfg, kind, weight_attr=attr())
            for kind in self.layer_types])
        self.norm = RMSNorm(hidden_size, eps=layer_norm_epsilon)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)
        self.mtp = nn.LayerList([
            MultiTokenPredictor(
                hidden_size, layer_norm_epsilon,
                lambda: nn.Sequential(*[
                    NemotronHLayer(cfg, kind, weight_attr=attr())
                    for kind in mtp_types]),
                weight_attr=attr())
            for _ in range(num_nextn_predict_layers)])


class HyperConnection(nn.Layer):
    """One sublayer's manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880; ``ops/hyper_connections.py`` holds the equations and
    the layout): the parameters ``phi [n C, 2 n + n^2]``, ``b`` and ``alpha =
    (pre, post, res)`` of the three maps, and the two halves of the residual
    path around a sublayer F on streams ``[B, T, n C]``:
    ``u, mix = read(streams)`` — the maps from the streams' own RMS-normed
    features, ``H_pre`` a sigmoid, ``H_post`` twice a sigmoid, ``H_res`` the
    Sinkhorn-Knopp projection of ``exp(clamp(.))`` onto the doubly stochastic
    matrices in ``iters`` rounds, and ``u = H_pre X`` for F's norm to read;
    ``write(streams, F(norm(u)), mix)`` gives ``H_res X + H_post^T y``.
    The maps' arithmetic is float32 whatever amp says, as a router's is.

    At initialisation no map is constant or saturated: ``phi ~ Normal(0,
    (n C)^-1/2)`` (the maps' pre-activations are spread by 1 over tokens),
    ``alpha = 1``, ``b = 0`` but for ``RES_DIAGONAL`` on the diagonal of
    ``H~_res`` (the residual mix leans to each stream keeping itself). ``b``
    and ``alpha`` are named for an optimizer's ``apply_decay_param_fun``.
    Scopes: ``mhc.maps`` / ``.sinkhorn`` / ``.pre`` / ``.post``."""

    #: the start of ``H~_res``'s diagonal
    RES_DIAGONAL = 2.0

    def __init__(self, hidden_size, n=4, sinkhorn_iters=20, eps=1e-6,
                 clamp=(-30.0, 30.0), rms_norm_eps=1e-6):
        super().__init__("mhc")
        from ..framework.param_attr import ParamAttr

        self.n, self.iters = int(n), int(sinkhorn_iters)
        self.eps, self.norm_eps = float(eps), float(rms_norm_eps)
        self.clamp = None if clamp is None else tuple(float(v) for v in clamp)
        wide, maps = n * hidden_size, 2 * n + n * n

        def named(name, initializer):
            return ParamAttr(name=f"{self.full_name()}.{name}",
                             initializer=initializer)

        self.phi = self.create_parameter(
            [wide, maps], attr=named("phi", nn.initializer.Normal(
                0.0, wide ** -0.5)))
        self.b = self.create_parameter(
            [maps], attr=named("b", nn.initializer.Constant(0.0)))
        bias = np.zeros(maps, np.float32)
        bias[2 * n:] = self.RES_DIAGONAL * np.eye(
            n, dtype=np.float32).reshape(-1)
        self.b.set_value(bias)
        self.alpha = self.create_parameter(
            [3], attr=named("alpha", nn.initializer.Constant(1.0)))

    def read(self, streams):
        """streams [B, T, n C] -> (u [B, T, C], (H_post, H_res))."""
        import jax

        from ..core.dispatch import apply_op
        from ..ops import hyper_connections as hc

        hc.count_sublayer(self.iters)
        with jax.named_scope("mhc.maps"):
            h = apply_op("mhc_maps", hc.maps, streams, self.phi, self.b,
                         self.alpha, n=self.n, eps=self.norm_eps)
        with jax.named_scope("mhc.sinkhorn"):
            h_pre, h_post, h_res = apply_op(
                "mhc_coefficients", hc.coefficients, h, n=self.n,
                iters=self.iters, eps=self.eps, clamp=self.clamp)
        with jax.named_scope("mhc.pre"):
            u = apply_op("mhc_pre", hc.pre, streams, h_pre, n=self.n)
        return u, (h_post, h_res)

    def write(self, streams, y, mix):
        import jax

        from ..core.dispatch import apply_op
        from ..ops import hyper_connections as hc

        with jax.named_scope("mhc.post"):
            return apply_op("mhc_post", hc.post, streams, y, *mix, n=self.n)


def _mhc_expand(x, n):
    import jax

    from ..core.dispatch import apply_op
    from ..ops import hyper_connections as hc

    with jax.named_scope("mhc.expand"):
        return apply_op("mhc_expand", hc.expand, x, n=n)


def _mhc_reduce(streams, n):
    import jax

    from ..core.dispatch import apply_op
    from ..ops import hyper_connections as hc

    with jax.named_scope("mhc.reduce"):
        return apply_op("mhc_reduce", hc.reduce, streams, n=n)


class Xing4DecoderLayer(JoyAIDecoderLayer):
    """A hyper-connected decoder block (Xing4.0; HF ``xing4_0``): the
    DeepSeek-V3 family's two sublayers — latent attention behind
    ``input_layernorm``, then the dense SwiGLU or the expert layer behind
    ``post_attention_layernorm`` — each INSIDE its own ``HyperConnection``
    (``attn_hc``, ``mlp_hc``): the block carries ``hc_mult`` residual
    streams ``[B, T, n C]`` in and out, a sublayer reads ``H_pre X`` and its
    output goes back through ``H_res X + H_post^T y``. ``own_streams``: the
    block takes and gives ONE hidden state, replicating it to the streams at
    its start and summing them at its end (the MTP module's block)."""

    def __init__(self, cfg, dense, weight_attr=None, own_streams=False):
        super().__init__(cfg, dense, weight_attr)
        self.hc_mult, self.own_streams = cfg["hc_mult"], bool(own_streams)

        def connection():
            return HyperConnection(
                cfg["hidden_size"], cfg["hc_mult"], cfg["hc_sinkhorn_iters"],
                cfg["hc_eps"], cfg["mhc_h_res_clamp"], cfg["rms_norm_eps"])

        self.attn_hc, self.mlp_hc = connection(), connection()

    def forward(self, x):
        if self.own_streams:
            x = _mhc_expand(x, self.hc_mult)
        u, mix = self.attn_hc.read(x)
        x = self.attn_hc.write(
            x, self.self_attn(self.input_layernorm(u)), mix)
        u, mix = self.mlp_hc.read(x)
        x = self.mlp_hc.write(
            x, self.mlp(self.post_attention_layernorm(u)), mix)
        return _mhc_reduce(x, self.hc_mult) if self.own_streams else x


class Xing4Model(_MTPDecoder):
    """Xing4.0 (XingChen-AGI, HF ``xing4_0``; Xing4.0-29B-A4B's sizes are
    the defaults): the DeepSeek-V3 family's decoder — latent attention in
    every block, ``first_k_dense_replace`` dense SwiGLU blocks, then expert
    blocks under a sigmoid router with a selection bias and a shared expert,
    an untied head, ``num_nextn_predict_layers`` multi-token-prediction
    modules — on a CHANGED RESIDUAL PATH: manifold-constrained
    hyper-connections (``Xing4DecoderLayer``, ``HyperConnection``). The
    embedding is replicated to ``hc_mult`` streams, every block carries
    them, and their SUM (arXiv:2409.19606) is what the final norm and the
    MTP module read; the module's block has streams of its own. Latent
    attention rotates by YaRN's table and scales its softmax by YaRN's
    factor (``rope_scaling``, ``MLAttention``).

    ``held_experts`` and ``held_attention_heads`` (each ``(first, count)``)
    give every layer this chip's share of a deployment that divides each
    layer over chips; each sublayer computes its own experts' or heads' part
    and that partial sum goes on. ``use_recompute`` runs each block under
    ``fleet.utils.recompute`` in a traced step. ``forward`` gives the main
    logits; a training loss takes ``training_features`` and
    ``lm_head.weight`` to ``mtp_lm_loss``."""

    #: the published ``rope_scaling`` group
    YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}

    def __init__(self, vocab_size=131072, hidden_size=3584,
                 num_hidden_layers=40, num_attention_heads=32,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 n_routed_experts=64, num_experts_per_tok=4,
                 n_shared_experts=1, first_k_dense_replace=2,
                 q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=YARN, norm_topk_prob=True,
                 routed_scaling_factor=2.0, num_nextn_predict_layers=1,
                 hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 bias_update_speed=0.001, balance_loss_weight=0.0,
                 initializer_range=0.02, held_experts=None,
                 held_rows_factor=2.0, held_attention_heads=None,
                 use_recompute=False):
        super().__init__(use_recompute)
        from ..framework.param_attr import ParamAttr

        def attr():
            return ParamAttr(initializer=nn.initializer.Normal(
                0.0, initializer_range))

        def held(share):
            return None if share is None else tuple(share)

        self.hc_mult = int(hc_mult)
        cfg = dict(
            hidden_size=hidden_size, num_attention_heads=num_attention_heads,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            n_routed_experts=n_routed_experts,
            num_experts_per_tok=num_experts_per_tok,
            n_shared_experts=n_shared_experts, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rms_norm_eps=rms_norm_eps, rope_theta=rope_theta,
            rope_scaling=rope_scaling, norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor,
            bias_update_speed=bias_update_speed,
            balance_loss_weight=balance_loss_weight,
            held_experts=held(held_experts),
            held_rows_factor=held_rows_factor,
            held_attention_heads=held(held_attention_heads),
            hc_mult=self.hc_mult, hc_sinkhorn_iters=hc_sinkhorn_iters,
            hc_eps=hc_eps,
            mhc_h_res_clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max))
        self.embed_tokens = nn.Embedding(vocab_size, hidden_size,
                                         weight_attr=attr())
        self.layers = nn.LayerList([
            Xing4DecoderLayer(cfg, dense=i < first_k_dense_replace,
                              weight_attr=attr())
            for i in range(num_hidden_layers)])
        self.norm = RMSNorm(hidden_size, eps=rms_norm_eps)
        self.lm_head = nn.Linear(hidden_size, vocab_size,
                                 weight_attr=attr(), bias_attr=False)
        self.mtp = nn.LayerList([
            MultiTokenPredictor(
                hidden_size, rms_norm_eps,
                lambda: Xing4DecoderLayer(cfg, dense=False,
                                          weight_attr=attr(),
                                          own_streams=True),
                weight_attr=attr())
            for _ in range(num_nextn_predict_layers)])

    def embed(self, input_ids):
        """The embedding, replicated to the streams: [B, T, hc_mult C]."""
        return _mhc_expand(self.embed_tokens(input_ids), self.hc_mult)

    def reduce(self, streams):
        """The streams' sum: what the final norm and the MTP module read."""
        return _mhc_reduce(streams, self.hc_mult)

    def _trunk(self, input_ids):
        x = self.embed(input_ids)
        for layer in self.layers:
            x = self._block(layer, x)
        return self.reduce(x)
