"""Transformer stack (reference: python/paddle/nn/layer/transformer.py:107
MultiHeadAttention, :1086 Transformer). Attention dispatches through
ops/attention.py: fused self-attention hands its packed QKV projection to
``packed_self_attention`` (the whole-sequence Pallas kernel on TPU when the
shapes allow), everything else goes through
``F.scaled_dot_product_attention``.
"""
from .. import functional as F
from ..layer import Layer
from .common import Linear, Dropout
from .norm import LayerNorm
from .container import LayerList


def _convert_attention_mask(attn_mask, dtype=None):
    """bool/int masks -> additive float masks (reference:
    python/paddle/nn/layer/transformer.py:90-105): True/nonzero keeps a
    position, False/0 masks it with a large negative bias. Float masks
    pass through (already additive)."""
    if attn_mask is None:
        return None
    import numpy as np
    import jax.numpy as jnp

    from ...core.tensor import Tensor

    arr = attn_mask._value if isinstance(attn_mask, Tensor) else attn_mask
    kind = jnp.result_type(arr)
    if jnp.issubdtype(kind, jnp.floating):
        return attn_mask
    target = jnp.dtype(dtype) if dtype is not None else jnp.float32
    additive = jnp.where(jnp.asarray(arr).astype(bool), 0.0, -1e9)\
        .astype(target)
    return Tensor(additive, stop_gradient=True) \
        if isinstance(attn_mask, Tensor) else additive


class MultiHeadAttention(Layer):
    """reference: nn/layer/transformer.py:107."""

    class Cache:
        def __init__(self, k, v):
            self.k = k
            self.v = v

    class StaticCache:
        def __init__(self, k, v):
            self.k = k
            self.v = v

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        from ... import tensor as pt

        b, s = x.shape[0], x.shape[1]
        x = pt.reshape(x, [b, s, self.num_heads, self.head_dim])
        return pt.transpose(x, [0, 2, 1, 3])

    def _merge_heads(self, x):
        from ... import tensor as pt

        b, h, s, d = x.shape
        return pt.reshape(pt.transpose(x, [0, 2, 1, 3]), [b, s, h * d])

    def gen_cache(self, key, value=None, type=None):
        from ... import tensor as pt

        if type is MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None else key))
            return self.StaticCache(k, v)
        if value is None:
            b = key.shape[0]
            k = pt.zeros([b, self.num_heads, 0, self.head_dim])
            v = pt.zeros([b, self.num_heads, 0, self.head_dim])
            return self.Cache(k, v)
        return self.Cache(key, value)

    def _fused_qkv(self, x):
        """Self-attention fast path: one [H, 3H] matmul instead of three
        [H, H] gemms — fewer kernel launches, larger MXU tile. Bitwise
        identical to the separate projections (each output element is
        the same dot product; concatenation only widens the gemm).
        Returns the packed [batch, seq, 3H] result, q | k | v."""
        from ... import tensor as pt

        w = pt.concat([self.q_proj.weight, self.k_proj.weight,
                       self.v_proj.weight], axis=1)
        qkv = pt.matmul(x, w)
        biases = [p.bias for p in (self.q_proj, self.k_proj, self.v_proj)]
        if all(b is not None for b in biases):
            qkv = qkv + pt.concat(biases, axis=0)
        return qkv

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        from ... import tensor as pt
        from ...ops import attention as attn_ops

        key = query if key is None else key
        value = key if value is None else value
        fusable = (key is query and value is key
                   and self.kdim == self.embed_dim == self.vdim
                   and not isinstance(cache, self.StaticCache)
                   and (self.q_proj.bias is None) == (self.k_proj.bias is None)
                   == (self.v_proj.bias is None))
        attn_mask = _convert_attention_mask(attn_mask)
        if fusable and cache is None:
            # the packed projection goes to attention as it is: the
            # whole-sequence kernel reads the heads in place, any other
            # route splits them there
            out = attn_ops.packed_self_attention(
                self._fused_qkv(query), self.num_heads, attn_mask=attn_mask,
                dropout_p=self.dropout, training=self.training)
        else:
            if fusable:
                q, k, v = (self._split_heads(x) for x in pt.split(
                    self._fused_qkv(query), 3, axis=-1))
            else:
                q = self._split_heads(self.q_proj(query))
                if isinstance(cache, self.StaticCache):
                    k, v = cache.k, cache.v
                else:
                    k = self._split_heads(self.k_proj(key))
                    v = self._split_heads(self.v_proj(value))
            if isinstance(cache, self.Cache):
                k = pt.concat([cache.k, k], axis=2)
                v = pt.concat([cache.v, v], axis=2)
                cache = self.Cache(k, v)
            out = self._merge_heads(F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
                training=self.training))
        out = self.out_proj(out)
        outs = [out]
        if self.need_weights:
            outs.append(None)
        if cache is not None and not isinstance(cache, self.StaticCache):
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1, activation="relu",
                 attn_dropout=None, act_dropout=None, normalize_before=False,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = activation

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        act = getattr(F, self.activation)
        src = self.linear2(self.dropout(act(self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList([encoder_layer] +
                                [copy.deepcopy(encoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1, activation="relu",
                 attn_dropout=None, act_dropout=None, normalize_before=False,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr, bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = activation

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask, cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            static_cache = None
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            static_cache = cache[1]
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        act = getattr(F, self.activation)
        tgt = self.linear2(self.dropout(act(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        if cache is None:
            return tgt
        return tgt, (incremental_cache, static_cache)

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory,
                                           type=MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList([decoder_layer] +
                                [copy.deepcopy(decoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask, memory_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        caches = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            caches = list(zip(*caches))
        return caches


class Transformer(Layer):
    """reference: nn/layer/transformer.py:1086."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation, attn_dropout,
                act_dropout, normalize_before, weight_attr, bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers, enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation, attn_dropout,
                act_dropout, normalize_before, weight_attr, bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers, dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        import numpy as np

        from ...core.tensor import Tensor

        mask = np.triu(np.full((length, length), -np.inf, np.float32), k=1)
        return Tensor(mask)
