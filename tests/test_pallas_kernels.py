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
    that long sequences run on the chip — the tests above stay within one
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
    the d dimension; every other test uses d <= 64). chip_smoke.py and
    the OLMoE cell run this config on the TPU — a wrong result here
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


# ----------------------------------- the streaming kernel's one-pass backward

def _ref_general(q, k, v, causal, seed, dropout_p):
    """float32 attention with a value width of its own, the bottom-right
    causal mask (row r sees keys <= r + seq_k - seq_q) and the kernel's
    counter-hash dropout mask."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask = jnp.tril(mask, k=sk - sq)
    # a row with no key at all (causal, seq_q > seq_k) attends to nothing
    p = jnp.where(mask, jax.nn.softmax(jnp.where(mask, s, -1e30), -1), 0.0)
    if dropout_p:
        keep = _hash_keep_np(
            seed, np.arange(b * h).reshape(b, h, 1, 1),
            np.arange(sq).reshape(1, 1, sq, 1),
            np.arange(sk).reshape(1, 1, 1, sk), sq, sk, dropout_p)
        p = jnp.where(jnp.asarray(keep), p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


#: (key width, value width, causal, seq_q, seq_k, dropout, dtype); blocks of
#: 32, so four k blocks meet every q block (fewer under the causal mask)
ONE_PASS_CASES = {
    "d64-causal-f32": (64, 64, True, 128, 128, 0.0, "float32"),
    "d64-full-bf16-p0.1": (64, 64, False, 128, 128, 0.1, "bfloat16"),
    "d128-causal-bf16": (128, 128, True, 128, 128, 0.0, "bfloat16"),
    "d128-full-f32-p0.1": (128, 128, False, 128, 128, 0.1, "float32"),
    "d192/128-causal-f32-p0.1": (192, 128, True, 128, 128, 0.1, "float32"),
    "d192/128-causal-bf16": (192, 128, True, 128, 128, 0.0, "bfloat16"),
    "d192/128-full-f32": (192, 128, False, 128, 128, 0.0, "float32"),
    "q<k-d64-causal-f32": (64, 64, True, 64, 128, 0.0, "float32"),
    "q<k-d192/128-causal-bf16-p0.1": (192, 128, True, 64, 128, 0.1,
                                      "bfloat16"),
    "q<k-d128-full-f32": (128, 128, False, 64, 128, 0.0, "float32"),
    # rows with no key at all: dq's slab rows are zeroed and written out
    # though no k block adds to them
    "q>k-d64-causal-f32": (64, 64, True, 128, 64, 0.0, "float32"),
}


@pytest.mark.parametrize("case", list(ONE_PASS_CASES))
def test_stream_one_pass_backward(case, monkeypatch):
    """dq, dk and dv from one set of score tiles (``flash_stream_bwd_dkv_dq``)
    are the two-call path's BITWISE — the same products in the same order
    into the same float32 accumulators — and a float32 reference's within
    the file's tolerances."""
    d_qk, d_v, causal, sq, sk, p_drop, dtype = ONE_PASS_CASES[case]
    rng = np.random.RandomState(31)
    q = jnp.array(rng.randn(1, 2, sq, d_qk) * 0.3, dtype)
    k = jnp.array(rng.randn(1, 2, sk, d_qk) * 0.3, dtype)
    v = jnp.array(rng.randn(1, 2, sk, d_v), dtype)
    seed = 4321

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            fn(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2))(q, k, v)

    def kernel(q, k, v):
        return mha(q, k, v, causal=causal, dropout_p=p_drop,
                   seed=jnp.int32(seed), block_q=32, block_k=32)

    assert fa._one_pass_backward(sq, d_qk, q.dtype.itemsize)
    one = grads(kernel)
    monkeypatch.setattr(fa, "_one_pass_backward", lambda *shape: False)
    two = grads(kernel)
    ref = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(_ref_general(
        q, k, v, causal, seed, p_drop))), argnums=(0, 1, 2))(
        *(a.astype(jnp.float32) for a in (q, k, v)))
    tol = 2e-3 if dtype == "float32" else 5e-2
    for a, b, r in zip(one, two, ref):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r),
                                   rtol=tol, atol=tol)


def test_one_pass_backward_is_chosen_by_shape():
    """The slab rule: a (batch . head) row of dq — float32 accumulator plus
    the double-buffered output block, lane-padded — within the budget. Every
    shape tests/test_mosaic_compile.py compiles for the chip takes one pass
    (both LM cells', their float32 checks); a row past the budget takes the
    two calls, and the rule turns exactly at the budget."""
    from test_mosaic_compile import STREAM_CASES, STREAM_TWO_CALL_CASES

    def one_pass(batch, heads, seq, head_dim, dtype, *dropout):
        d_qk = head_dim[0] if isinstance(head_dim, tuple) else head_dim
        return fa._one_pass_backward(seq, d_qk, jnp.dtype(dtype).itemsize)

    assert all(one_pass(*shape) for shape in STREAM_CASES.values())
    assert not any(one_pass(*shape)
                   for shape in STREAM_TWO_CALL_CASES.values())
    # 192-wide keys take two lane groups, like 256-wide ones
    assert (fa._one_pass_backward(8192, 192, 2)
            and not fa._one_pass_backward(32768, 192, 2))
    rows = fa._ONE_PASS_SLAB_BUDGET // (256 * (4 + 2 * 2))
    assert fa._one_pass_backward(rows, 256, 2)
    assert not fa._one_pass_backward(rows + 8, 256, 2)
    assert fa._ONE_PASS_SLAB_BUDGET < fa._ONE_PASS_VMEM_LIMIT


@pytest.mark.parametrize("calls", ["one", "two"])
def test_stream_backward_counter_names_the_path(calls, monkeypatch):
    """``paddle_tpu_flash_stream_backward_total{calls}`` counts each traced
    backward under the path it took, and a forward alone counts nothing."""
    if calls == "two":
        monkeypatch.setattr(fa, "_one_pass_backward", lambda *shape: False)
    rng = np.random.RandomState(8)
    q = jnp.array(rng.randn(1, 1, 64, 16), jnp.float32)

    def count():
        return {c: fa._BACKWARD_TOTAL.value(calls=c) for c in ("one", "two")}

    before = count()
    mha(q, q, q, causal=True, block_q=32, block_k=32)
    assert count() == before
    jax.grad(lambda q: jnp.sum(mha(q, q, q, causal=True, block_q=32,
                                   block_k=32)))(q)
    after = count()
    assert after[calls] == before[calls] + 1
    other = "two" if calls == "one" else "one"
    assert after[other] == before[other]


# ------------------------------------------- whole-sequence ("short") kernel

from paddle_tpu.ops import attention  # noqa: E402

mha_packed = functools.partial(fa.mha_packed, interpret=True)


def _split_packed(qkv, heads):
    b, s, e3 = qkv.shape
    d = e3 // 3 // heads
    return [x.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
            for x in jnp.split(qkv, 3, axis=-1)]


def _merge_heads(o):
    b, h, s, d = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _packed_sdpa_ref(qkv, heads):
    """split heads -> ops/attention.py::_sdpa_ref -> merge heads."""
    q, k, v = _split_packed(qkv, heads)
    return _merge_heads(attention._sdpa_ref(
        q, k, v, None, None, scale=1.0 / np.sqrt(q.shape[-1]),
        dropout_p=0.0, is_causal=False))


def _packed_ref_dropout(qkv, heads, seed, dropout_p):
    """The reference with the mask the kernel used: head h of row b hashes
    batch-head index b * heads + h, rows = queries, cols = keys."""
    q, k, v = _split_packed(qkv, heads)
    b, h, s, d = q.shape
    p = jax.nn.softmax(jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d), -1)
    keep = _hash_keep_np(
        seed, np.arange(b * h).reshape(b, h, 1, 1),
        np.arange(s).reshape(1, 1, s, 1), np.arange(s).reshape(1, 1, 1, s),
        s, s, dropout_p)
    p = jnp.where(jnp.asarray(keep), p / (1.0 - dropout_p), 0.0)
    return _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", p, v))


def _packed_input(seed, batch, seq, heads, head_dim, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(batch, seq, 3 * heads * head_dim) * 0.5,
                       dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", [128, 256, 384])
def test_short_matches_sdpa_ref(seq, head_dim, dtype, tol):
    """Forward and dq/dk/dv (one packed gradient) against _sdpa_ref
    (test_short_rows_per_program has the row counts that are no multiple
    of the preferred block)."""
    heads = 256 // head_dim
    qkv = _packed_input(seq + head_dim, 3, seq, heads, head_dim, dtype)
    wide = qkv.astype(jnp.float32)
    out = mha_packed(qkv, heads)
    assert out.dtype == dtype and out.shape == (3, seq, 256)
    ref = _packed_sdpa_ref(wide, heads)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)

    def loss(fn):
        return lambda x: jnp.sum(jnp.sin(fn(x).astype(jnp.float32)))

    got = jax.grad(loss(lambda x: mha_packed(x, heads)))(qkv)
    want = jax.grad(loss(lambda x: _packed_sdpa_ref(x, heads)))(wide)
    assert got.dtype == dtype
    for g, w, name in zip(jnp.split(got.astype(jnp.float32), 3, -1),
                          jnp.split(want, 3, -1), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol, err_msg=name)


@pytest.mark.parametrize("batch,fwd_rows,bwd_rows",
                         [(6, 6, 6), (26, 13, 13), (28, 14, 7), (29, 1, 1)])
def test_short_rows_per_program(batch, fwd_rows, bwd_rows):
    """Rows a program: the largest divisor of the batch whose blocks fit
    the budget (23 rows forward, 13 backward at this shape) — down to 1
    for a prime batch; the result does not depend on it."""
    heads, seq = 2, 128
    row = functools.partial(fa._short_row_bytes, seq, heads, 64, 4)
    assert fa._short_rows(batch, row(fa._SHORT_FWD_BLOCKS)) == fwd_rows
    assert fa._short_rows(batch, row(fa._SHORT_BWD_BLOCKS)) == bwd_rows
    qkv = _packed_input(1, batch, seq, heads, 64)

    def loss(fn):
        return lambda x: jnp.sum(jnp.sin(fn(x)))

    np.testing.assert_allclose(
        np.asarray(mha_packed(qkv, heads)),
        np.asarray(_packed_sdpa_ref(qkv, heads)), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(
        np.asarray(jax.grad(loss(lambda x: mha_packed(x, heads)))(qkv)),
        np.asarray(jax.grad(loss(lambda x: _packed_sdpa_ref(x, heads)))(qkv)),
        rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("shape,heads", [((2, 100, 384), 2),   # seq % 128
                                         ((2, 640, 384), 2),   # seq > 512
                                         ((2, 128, 3 * 96), 2),  # head 48
                                         ((2, 128, 3 * 192), 3),  # 3 x 64
                                         ((1, 512, 3 * 2048), 32)])  # VMEM
def test_short_refuses_other_shapes(shape, heads):
    e = shape[-1] // 3
    assert not fa.short_supported(shape[1], heads, e // heads, jnp.float32)
    with pytest.raises(ValueError, match="short_supported"):
        mha_packed(jnp.zeros(shape, jnp.float32), heads)


def test_short_dropout_matches_hash_reference():
    """Output and gradients equal a reference that applies the mask the
    kernel used — so forward and backward regenerate the same mask."""
    heads, p_drop, seed = 2, 0.2, 4321
    qkv = _packed_input(8, 3, 128, heads, 64)
    out = mha_packed(qkv, heads, dropout_p=p_drop, seed=jnp.int32(seed))
    ref = _packed_ref_dropout(qkv, heads, seed, p_drop)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)

    def loss(fn):
        return lambda x: jnp.sum(jnp.sin(fn(x)))

    got = jax.grad(loss(lambda x: mha_packed(
        x, heads, dropout_p=p_drop, seed=jnp.int32(seed))))(qkv)
    want = jax.grad(loss(lambda x: _packed_ref_dropout(
        x, heads, seed, p_drop)))(qkv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def _short_keep_bits(seed, p_drop, batch=2, seq=128, heads=2, head_dim=64):
    """q = k = 0 makes every row uniform and v = e_j picks column j: the
    output IS the mask (1 / (seq * keep) where kept), read one key at a
    time — here for key 0 of every (row, head, query)."""
    e = heads * head_dim
    v = jnp.zeros((batch, seq, e), jnp.float32).at[:, 0, :].set(1.0)
    qkv = jnp.concatenate([jnp.zeros((batch, seq, 2 * e), jnp.float32), v],
                          axis=-1)
    out = mha_packed(qkv, heads, dropout_p=p_drop, seed=seed)
    return np.asarray(out[:, :, ::head_dim]) > 0     # [batch, seq, heads]


def test_short_dropout_statistics_and_determinism():
    """Keep fraction within binomial bounds; same seed -> same output;
    another seed -> another mask."""
    heads, p_drop = 2, 0.1
    # v = 1, q = k = 0: out[b, s, :] of head h = kept share of the row
    e = heads * 64
    qkv = jnp.concatenate([jnp.zeros((4, 128, 2 * e), jnp.float32),
                           jnp.ones((4, 128, e), jnp.float32)], axis=-1)
    out = mha_packed(qkv, heads, dropout_p=p_drop, seed=jnp.int32(7))
    kept = np.asarray(out[:, :, ::64], np.float64) * (1 - p_drop)
    n = 4 * heads * 128 * 128
    sigma = np.sqrt(p_drop * (1 - p_drop) / n)
    assert abs(kept.mean() - (1 - p_drop)) < 4 * sigma, kept.mean()
    assert kept.min() > 0.6 and kept.max() <= 1.0 + 1e-6  # per row too

    x = _packed_input(9, 2, 128, heads, 64)
    a = mha_packed(x, heads, dropout_p=p_drop, seed=jnp.int32(11))
    b = mha_packed(x, heads, dropout_p=p_drop, seed=jnp.int32(11))
    c = mha_packed(x, heads, dropout_p=p_drop, seed=jnp.int32(12))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("word", [0, 1], ids=["first-word", "last-word"])
def test_kernel_seed_folds_every_key_word(word):
    """Two keys that differ in one word only — the first as much as the
    last — give different kernel seeds and different masks (ADVICE: the
    seed used to be the key's last word alone)."""
    words = np.array([[123, 456], [123, 456]], np.uint32)
    words[1, word] ^= 0x10
    k0, k1 = (jax.random.wrap_key_data(jnp.asarray(w)) for w in words)
    s0, s1 = attention._kernel_seed(k0), attention._kernel_seed(k1)
    assert s0.dtype == jnp.int32 and int(s0) != int(s1)
    m0, m1 = _short_keep_bits(s0, 0.5), _short_keep_bits(s1, 0.5)
    assert 0.35 < m0.mean() < 0.65
    assert (m0 != m1).mean() > 0.3


def test_short_mask_hashes_the_rows_own_numbers():
    """A caller that holds one shard of the batch passes that shard's row
    numbers and draws the mask the unsharded call would: the result does
    not depend on how the batch was split."""
    qkv = _packed_input(12, 4, 128, 2, 64)
    whole = mha_packed(qkv, 2, dropout_p=0.3, seed=jnp.int32(5))
    shard = mha_packed(qkv[2:], 2, dropout_p=0.3, seed=jnp.int32(5),
                       row_ids=jnp.arange(2, 4))
    assert np.array_equal(np.asarray(whole[2:]), np.asarray(shard))
    again = mha_packed(qkv[2:], 2, dropout_p=0.3, seed=jnp.int32(5))
    assert not np.allclose(np.asarray(whole[2:]), np.asarray(again))
