"""Pallas flash-attention kernel vs the jnp reference attention.

Runs the real kernels in the Pallas interpreter on CPU, asked for
explicitly (``interpret=True``); chip_smoke.py phase 3 compiles the same
code with Mosaic on the chip.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

mha = functools.partial(fa.mha, interpret=True)


def _ref(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    rng = np.random.RandomState(0)
    B, H, S, D = 1, 2, 64, 16
    q = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    o = mha(q, k, v, causal=causal, block_q=32, block_k=32)
    r = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    rng = np.random.RandomState(1)
    B, H, S, D = 1, 1, 64, 16
    q = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)

    def loss_f(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    gf = jax.grad(loss_f(lambda q, k, v: mha(
        q, k, v, causal=causal, block_q=32, block_k=32)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_f(lambda q, k, v: _ref(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_cross_attention_lengths(causal):
    # causal with seq_q != seq_k is the KV-cache decode case: bottom-right
    # aligned mask (query i sees keys <= i + seq_k - seq_q), matching
    # _sdpa_ref's jnp.tril(..., k=s_k - s_q)
    rng = np.random.RandomState(2)
    q = jnp.array(rng.randn(1, 2, 32, 16), jnp.float32)
    k = jnp.array(rng.randn(1, 2, 64, 16), jnp.float32)
    v = jnp.array(rng.randn(1, 2, 64, 16), jnp.float32)
    o = mha(q, k, v, causal=causal, block_q=32, block_k=32)

    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((32, 64), bool), k=64 - 32)
        s = jnp.where(mask, s, -1e30)
    r = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_single_query():
    # 1 query over a long KV cache must attend ALL keys under causal
    rng = np.random.RandomState(4)
    q = jnp.array(rng.randn(1, 2, 8, 16), jnp.float32)
    k = jnp.array(rng.randn(1, 2, 64, 16), jnp.float32)
    v = jnp.array(rng.randn(1, 2, 64, 16), jnp.float32)
    o = mha(q, k, v, causal=True, block_q=8, block_k=32)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((8, 64), bool), k=64 - 8)
    s = jnp.where(mask, s, -1e30)
    r = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-4, atol=2e-4)


def _hash_keep_np(seed, b, rows, cols, seq_q, seq_k, dropout_p):
    """numpy twin of fa._keep_mask for exact-match testing."""
    with np.errstate(over="ignore"):
        bseed = np.uint32(seed) ^ (b.astype(np.uint32) * np.uint32(0x85EBCA6B))
        bseed ^= bseed >> np.uint32(13)
        bseed *= np.uint32(0xC2B2AE35)
        idx = (rows * seq_k + cols).astype(np.uint32)
        h = idx * np.uint32(0x9E3779B1) ^ bseed
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    thresh = np.uint32(min(int((1.0 - dropout_p) * 2**32), 2**32 - 1))
    return h < thresh


def _ref_dropout(q, k, v, seed, dropout_p):
    """Reference attention applying the SAME counter-hash dropout mask."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    p = jax.nn.softmax(s, axis=-1)
    bh_idx = np.arange(b * h).reshape(b, h, 1, 1)
    rows = np.arange(sq).reshape(1, 1, sq, 1)
    cols = np.arange(sk).reshape(1, 1, 1, sk)
    keep = _hash_keep_np(seed, bh_idx, rows, cols, sq, sk, dropout_p)
    p = jnp.where(jnp.asarray(keep), p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_flash_dropout_matches_hash_reference():
    rng = np.random.RandomState(5)
    B, H, S, D = 1, 2, 64, 16
    p_drop, seed = 0.2, 1234
    q = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    o = mha(q, k, v, dropout_p=p_drop, seed=jnp.int32(seed),
               block_q=32, block_k=32)
    r = _ref_dropout(q, k, v, seed, p_drop)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-4, atol=2e-4)
    # dropout actually drops something
    o0 = mha(q, k, v, block_q=32, block_k=32)
    assert not np.allclose(np.asarray(o), np.asarray(o0))


def test_flash_dropout_grads_match_hash_reference():
    rng = np.random.RandomState(6)
    B, H, S, D = 1, 1, 64, 16
    p_drop, seed = 0.15, 77
    q = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)

    def loss_f(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    gf = jax.grad(loss_f(lambda q, k, v: mha(
        q, k, v, dropout_p=p_drop, seed=jnp.int32(seed),
        block_q=32, block_k=32)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_f(lambda q, k, v: _ref_dropout(q, k, v, seed, p_drop)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_bfloat16():
    rng = np.random.RandomState(3)
    q = jnp.array(rng.randn(1, 1, 64, 16), jnp.bfloat16)
    k = jnp.array(rng.randn(1, 1, 64, 16), jnp.bfloat16)
    v = jnp.array(rng.randn(1, 1, 64, 16), jnp.bfloat16)
    o = mha(q, k, v, causal=True, block_q=32, block_k=32)
    r = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
             v.astype(jnp.float32), True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_multiblock_long_seq(causal):
    """S=512 = 4 q-blocks x 4 k-blocks of 128: the multi-block
    accumulation path (online softmax across k blocks, dq/dkv loops)
    that the seq-4k flash bench runs — the tests above stay within one
    block and would miss cross-block bugs."""
    rng = np.random.RandomState(7)
    B, H, S, D = 1, 1, 512, 16
    q = jnp.array(rng.randn(B, H, S, D) * 0.3, jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D) * 0.3, jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    out = mha(q, k, v, causal=causal)
    ref = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_fa(q, k, v):
        return jnp.sum(jnp.sin(mha(q, k, v, causal=causal)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref(q, k, v, causal)))

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_flash_fully_masked_rows_zero():
    """Causal with seq_q > seq_k: rows whose causal window is empty must
    produce o = 0 with zero gradient — not exp(0)=1 uniform attention
    (the online-softmax degenerate case where the running max never
    leaves NEG_INF). Covers both the block-aligned and the
    straddling-block layout of the masked region."""
    rng = np.random.RandomState(11)
    B, H, SQ, SK, D = 1, 1, 128, 64, 16
    q = jnp.array(rng.randn(B, H, SQ, D) * 0.3, jnp.float32)
    k = jnp.array(rng.randn(B, H, SK, D) * 0.3, jnp.float32)
    v = jnp.array(rng.randn(B, H, SK, D), jnp.float32)
    # rows 0..SK-1 attend to nothing (offset = SK - SQ = -64). The module
    # _ref uses top-left causal alignment; mha is bottom-right-aligned
    # (row r attends cols <= r + seq_k - seq_q), so build the reference
    # with that mask directly.
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((SQ, SK), bool), k=SK - SQ)
    ref = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1), v)
    for bq, bk in [(64, 64), (128, 64)]:  # aligned / straddling
        out = mha(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out[:, :, SK:]),
                                   np.asarray(ref[:, :, SK:]),
                                   rtol=2e-4, atol=2e-4)
        assert float(jnp.abs(out[:, :, :SK]).max()) == 0.0

        g = jax.grad(lambda q: jnp.sum(mha(q, k, v, causal=True,
                                              block_q=bq,
                                              block_k=bk)))(q)
        assert float(jnp.abs(g[:, :, :SK]).max()) == 0.0


@pytest.mark.parametrize("causal", [False, True])
def test_flash_head_dim_128(causal):
    """head_dim 128 = the Llama attention shape (two full lane groups in
    the d dimension; every other test uses d <= 64). The llama_2048 and
    flash d128 benches run this config on the TPU — a wrong result here
    must fail in-suite (Mosaic lowering itself is chip_smoke phase 3)."""
    rng = np.random.RandomState(3)
    B, H, S, D = 1, 2, 512, 128
    q = jnp.array(rng.randn(B, H, S, D) * 0.2, jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D) * 0.2, jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    out = mha(q, k, v, causal=causal)
    ref = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g_fa = jax.grad(lambda q, k, v: jnp.sum(
        jnp.sin(mha(q, k, v, causal=causal))), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        jnp.sin(_ref(q, k, v, causal))), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
