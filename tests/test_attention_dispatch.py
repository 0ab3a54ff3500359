"""The Pallas-vs-XLA attention dispatch gate (ops/attention.py).

Round-5 v5e measurement: at seq 128 the flash kernel is 3x slower than
XLA's batched-matmul attention (per-program overhead), while at long
seq XLA's S^2 logits buffer explodes and the kernel wins. The gate —
kernel when seq_k >= pallas_attention_min_seq OR seq_q*seq_k >=
min_seq^2 — is pinned here, and so is what happens when a selected
kernel fails: it raises (no fallback to the XLA path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch, flags
from paddle_tpu.ops import attention


@pytest.fixture(autouse=True)
def _seed():
    paddle.seed(0)
    # the dispatch layer caches the jitted op per (name, shape); evict so
    # each test's monkeypatched kernel is actually (re)traced
    dispatch.evict_ops("flash_attention")
    dispatch.evict_ops("sdpa")


@pytest.fixture
def track_kernel(monkeypatch):
    """Count flash-kernel entries without changing its output."""
    from paddle_tpu.ops.pallas import flash_attention

    calls = []
    real = flash_attention.mha

    def spy(*args, **kwargs):
        calls.append(kwargs.get("causal"))
        return real(*args, **kwargs)

    monkeypatch.setattr(flash_attention, "mha", spy)
    return calls


@pytest.fixture(autouse=True)
def _interpret_kernels():
    # the kernel is selected on a TPU, or when the interpreter is asked
    # for explicitly — tests run on CPU
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags({"pallas_interpret": False})


def _qkv(sq, sk, d=16):
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(1, 2, sq, d), jnp.float32),
            jnp.asarray(rng.randn(1, 2, sk, d), jnp.float32),
            jnp.asarray(rng.randn(1, 2, sk, d), jnp.float32))


def test_short_seq_routes_to_xla(track_kernel):
    q, k, v = _qkv(128, 128)
    attention.scaled_dot_product_attention(q, k, v, training=False)
    assert track_kernel == []


def test_long_k_routes_to_kernel(track_kernel):
    q, k, v = _qkv(64, 2048)
    attention.scaled_dot_product_attention(q, k, v, training=False)
    assert len(track_kernel) == 1


def test_long_q_short_k_stays_on_xla(track_kernel):
    # kernel overhead is governed by seq_k; XLA's logits are small here
    q, k, v = _qkv(2048, 128)
    attention.scaled_dot_product_attention(q, k, v, training=False)
    assert track_kernel == []


def test_huge_product_routes_to_kernel(track_kernel):
    # both sides below min_seq individually, but the logits buffer is
    # min_seq^2-scale: kernel avoids the S^2 materialisation
    q, k, v = _qkv(4096, 512)
    attention.scaled_dot_product_attention(q, k, v, training=False)
    assert len(track_kernel) == 1


def test_flag_zero_always_kernel(track_kernel):
    paddle.set_flags({"pallas_attention_min_seq": 0})
    try:
        q, k, v = _qkv(64, 64)
        attention.scaled_dot_product_attention(q, k, v, training=False)
        assert len(track_kernel) == 1
    finally:
        paddle.set_flags({"pallas_attention_min_seq": 1024})


def test_paths_numerically_agree(track_kernel):
    q, k, v = _qkv(64, 2048)
    out_kernel = attention.scaled_dot_product_attention(q, k, v,
                                                        training=False)
    assert len(track_kernel) == 1
    ref = attention._sdpa_ref(q, k, v, None, None,
                              scale=1.0 / np.sqrt(16), dropout_p=0.0,
                              is_causal=False)
    kv = out_kernel._value if hasattr(out_kernel, "_value") else out_kernel
    np.testing.assert_allclose(np.asarray(kv), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_failure_raises(monkeypatch, recwarn):
    """A selected kernel that fails is an error — never a warning plus
    the XLA path, which would report a broken kernel as a slow one."""
    from paddle_tpu.ops.pallas import flash_attention

    def boom(*a, **kw):
        raise RuntimeError("kernel exploded")

    monkeypatch.setattr(flash_attention, "mha", boom)
    q, k, v = _qkv(64, 2048)
    for _ in range(2):  # the failure is not remembered either
        with pytest.raises(RuntimeError, match="kernel exploded"):
            attention.scaled_dot_product_attention(q, k, v, training=False)
    assert not [w for w in recwarn if "falling back" in str(w.message)]


def test_kernel_not_selected_off_tpu_without_the_flag():
    paddle.set_flags({"pallas_interpret": False})
    assert attention._use_pallas() is False  # CPU backend, no flag
    paddle.set_flags({"pallas_interpret": True})
    assert attention._use_pallas() is True
    paddle.set_flags({"use_pallas_kernels": False})
    try:
        assert attention._use_pallas() is False
    finally:
        paddle.set_flags({"use_pallas_kernels": True})
