"""Ring attention: exact attention over sequence-sharded q/k/v.

Long-context sequence/context parallelism is green-field relative to the
reference (SURVEY §5: no ring attention/sequence-parallel anywhere in the
tree); the TPU-native design is the Ring Attention recurrence (blockwise
online softmax across devices) expressed with `shard_map` + `ppermute`
so each hop rides one ICI neighbour link:

- q, k, v are sharded on the sequence dim over the `sp` mesh axis;
- each of the n ring steps computes the local q block against the
  currently-held k/v block, folds it into the running (max, sum, acc)
  online-softmax state, then rotates k/v one device to the right with
  `lax.ppermute`;
- causal masking uses global positions (device index × local seq len),
  so the result is exactly single-device causal attention;
- everything is jnp + lax collectives: reverse-mode AD falls out of
  `lax.scan`'s and `ppermute`'s transpose rules — no custom VJP needed.

Per-device memory is O(S_local² + S_local·D) and the S²·D FLOPs are
spread n ways, so sequence length scales linearly with the ring size.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def _ring_attn_shard(q, k, v, *, axis_name, n_shards, scale, causal):
    """Per-device body under shard_map. q,k,v: [B, H, S_local, D]."""
    idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    qf = q.astype(jnp.float32) * scale

    # constants start "unvarying" under shard_map's vma typing; the carry
    # becomes device-varying after step 1, so cast the initial state too
    def _varying(x):
        try:
            return lax.pcast(x, (axis_name,), to="varying")
        except (AttributeError, TypeError):
            return x

    m0 = _varying(jnp.full(q.shape[:3] + (1,), -1e30, jnp.float32))
    l0 = _varying(jnp.zeros(q.shape[:3] + (1,), jnp.float32))
    acc0 = _varying(jnp.zeros(qf.shape, jnp.float32))
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def fold(i, k_blk, v_blk, m, l, acc):
        # the block we hold at step i originated on device (idx - i) mod n
        src = (idx - i) % n_shards
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal:
            rows = idx * s_local + lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 0)
            cols = src * s_local + lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1)
            s = jnp.where((rows >= cols)[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # step 0 on the local block, then n-1 rotate-and-fold steps: exactly
    # n-1 ppermute hops (the nth rotation would only feed a dead carry)
    m, l, acc = fold(jnp.int32(0), k, v, m0, l0, acc0)

    def step(carry, i):
        k_blk, v_blk, m, l, acc = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        m, l, acc = fold(i, k_blk, v_blk, m, l, acc)
        return (k_blk, v_blk, m, l, acc), None

    if n_shards > 1:
        (k_f, v_f, m, l, acc), _ = lax.scan(
            step, (k, v, m, l, acc), jnp.arange(1, n_shards))
        del k_f, v_f
    l = jnp.maximum(l, 1e-30)
    return (acc / l).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="sp", scale=None,
                   causal=False):
    """Exact attention with q/k/v sequence-sharded over `axis_name`.

    q, k, v: [batch, heads, seq, head_dim] GLOBAL arrays (jit will keep
    them sharded on seq); seq must divide evenly by the axis size.
    """
    from ..distributed import topology

    mesh = mesh or topology.get_global_mesh()
    n = mesh.shape.get(axis_name, 1)
    if n == 1:
        # degenerate ring: plain blockwise attention on one device
        return _dispatch_ring(q, k, v, axis_name, 1, scale, causal)

    spec = P(None, None, axis_name, None)
    fn = functools.partial(_dispatch_ring, axis_name=axis_name, n=n,
                           scale=scale, causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def ring_attention_in_shard_map(q, k, v, axis_name="sp", scale=None,
                                causal=False):
    """Ring attention for code ALREADY inside a shard_map whose manual
    axes include ``axis_name`` (e.g. a pipeline stage interior — the
    pp x sp long-context composition): calls the per-device ring body
    directly instead of opening a second, un-nestable shard_map.
    q, k, v: [B, H, S_local, D] local sequence shards. The shard count
    comes from the MANUAL CONTEXT itself (lax.axis_size — static), not
    the global mesh, so a mesh= mismatch cannot silently degrade to
    block-diagonal local attention. Outside any manual context (or
    axis size 1) it falls back to plain local attention (the 1-device
    oracle)."""
    try:
        n = jax.lax.axis_size(axis_name)
    except NameError:
        n = 1  # not inside a manual context carrying this axis
    return _dispatch_ring(q, k, v, axis_name, n, scale, causal)


def _dispatch_ring(q, k, v, axis_name, n, scale, causal):
    """Shared resolve-and-dispatch for both entry points."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        return _ring_attn_local(q, k, v, scale=scale, causal=causal)
    return _ring_attn_shard(q, k, v, axis_name=axis_name, n_shards=n,
                            scale=float(scale), causal=bool(causal))


def _ring_attn_local(q, k, v, *, scale, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((rows + (sk - sq) >= cols)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
