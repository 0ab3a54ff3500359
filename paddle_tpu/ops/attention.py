"""Scaled-dot-product attention: jnp reference + Pallas flash kernel switch.

The reference has no flash attention (SURVEY §5 long-context: absent) —
its closest analog is the fused BERT encoder functor
(reference: paddle/fluid/operators/math/bert_encoder_functor.cu). Here the
TPU-native design is a Pallas blockwise-softmax kernel (ops/pallas/
flash_attention.py) selected on TPU, with this jnp implementation as the
portable reference; XLA already fuses it into few kernels on TPU.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import flags, random as random_core
from ..core.dispatch import apply_op
from ..distributed import topology


def _sdpa_ref(q, k, v, mask, key, *, scale, dropout_p, is_causal,
              fp32_softmax=True):
    # q,k,v: [batch, heads, seq, head_dim]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if not jnp.issubdtype(mask.dtype, jnp.floating):
            # bool/int keep-masks (reference converts via
            # _convert_attention_mask; adding raw 0/1 ints would bias
            # logits instead of masking)
            logits = jnp.where(mask.astype(bool), logits,
                               jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    if fp32_softmax:
        probs = (jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
                 .astype(q.dtype))
    else:  # keep the q dtype: halves softmax HBM traffic under amp (an
        # f32 additive mask can still have promoted the logits — cast
        # back so both flag settings agree on the output dtype)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        # counter-hash mask, not threefry bernoulli (core/random.py
        # fast_keep_mask): attention-prob masks dominate dropout RNG cost
        keep = random_core.fast_keep_mask(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _use_pallas():
    """Kernel selection is a function of what can be observed: the
    use_pallas_kernels flag and the platform (a TPU, or the
    pallas_interpret flag the CPU tests and the chip_smoke dry run set).
    A platform query that fails is an error, not "no kernel"."""
    if not flags.flag_value("use_pallas_kernels"):
        return False
    if flags.flag_value("pallas_interpret"):
        return True
    from ..core.place import is_tpu_available

    return is_tpu_available()


def _flash(q, k, v, key, *, scale, is_causal, dropout_p, interpret):
    """The Pallas kernel on [batch, heads, seq, head_dim]. Inside a step
    being traced for a multi-device mesh (topology.traced_mesh) the call
    is wrapped in a shard_map: GSPMD cannot partition a Mosaic kernel,
    every mesh axis has to be manual around it. (batch, head) programs
    are independent, so batch shards over the data axes and heads over
    'mp' — each only where it divides; otherwise that dim is computed
    whole on every device of the axis."""
    from .pallas import flash_attention

    seed = (jnp.zeros((), jnp.int32) if key is None else
            jax.random.key_data(key).reshape(-1)[-1].astype(jnp.int32))
    kernel = functools.partial(
        flash_attention.mha, scale=scale, causal=is_causal,
        dropout_p=dropout_p, interpret=interpret)
    mesh = topology.traced_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, seed=seed)

    data = topology.data_axes(mesh)
    n_data = math.prod(mesh.shape[ax] for ax in data)
    n_mp = mesh.shape.get("mp", 1)
    b_axes = data if n_data > 1 and q.shape[0] % n_data == 0 else ()
    h_axes = ("mp",) if n_mp > 1 and q.shape[1] % n_mp == 0 else ()
    spec = P(b_axes or None, h_axes or None, None, None)

    def on_shard(q, k, v, seed):
        # the mask hash counts (batch, head) from 0 on every shard: give
        # each shard its own seed or they all drop the same entries
        for ax in b_axes + h_axes:
            seed = seed * jnp.int32(mesh.shape[ax]) + jax.lax.axis_index(ax)
        return kernel(q, k, v, seed=seed)

    return jax.shard_map(on_shard, mesh=mesh, in_specs=(spec, spec, spec, P()),
                         out_specs=spec, check_vma=False)(q, k, v, seed)


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True):
    head_dim = q.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)
    p = float(dropout_p) if training else 0.0
    key = random_core.next_key() if p > 0.0 else None

    # seq-length dispatch threshold: below it, XLA's own fused attention
    # runs (at one 128-block per program the kernel is overhead-bound and
    # 3x slower than XLA's batched matmul — v5e measurement in the flag's
    # help text; 0 = always use the kernel). Kernel overhead is governed
    # by seq_k (the per-program inner-loop length); XLA's memory blowup
    # by the seq_q*seq_k logits buffer. So: kernel when the k side is
    # long, OR when the logits product is as big as a min_seq^2 square
    # (long-q/short-k stays on XLA — its logits are small and the kernel
    # would be one k-block per program again).
    min_seq = flags.flag_value("pallas_attention_min_seq")
    seq_q, seq_k = q.shape[-2], k.shape[-2]
    kernel_pays = seq_k >= min_seq or seq_q * seq_k >= min_seq * min_seq
    if kernel_pays and attn_mask is None and _use_pallas():
        # a selected kernel that fails raises: falling back to the XLA
        # path would report a broken kernel as a slow one. interpret
        # rides the static kwargs so a flag flip retraces.
        return apply_op(
            "flash_attention", _flash, q, k, v, key,
            scale=scale, is_causal=bool(is_causal), dropout_p=p,
            interpret=bool(flags.flag_value("pallas_interpret")))

    # the flag rides the static kwargs so the per-(op, shape) dispatch
    # cache keys on it — a flag flip must not serve a stale trace
    return apply_op(
        "sdpa", _sdpa_ref, q, k, v, attn_mask, key,
        scale=scale, dropout_p=p, is_causal=bool(is_causal),
        fp32_softmax=bool(flags.flag_value("sdpa_softmax_fp32")))
