"""sdpa_softmax_fp32 flag: bf16 attention softmax must not break
convergence (the accuracy half of that lever — the throughput half is
a chip measurement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core import dispatch
from paddle_tpu.ops import attention


@pytest.fixture(autouse=True)
def _reset():
    # (no dispatch eviction needed: the sdpa cache keys on the flag via
    # its static kwargs)
    yield
    paddle.set_flags({"sdpa_softmax_fp32": True})


def _train(fp32_softmax, steps=25):
    """Train under amp O1 so the attention logits really are bf16 —
    without auto_cast both flag settings compute identical f32 softmax
    and the comparison proves nothing."""
    paddle.set_flags({"sdpa_softmax_fp32": bool(fp32_softmax)})
    paddle.seed(11)
    enc = nn.TransformerEncoder(
        nn.TransformerEncoderLayer(d_model=32, nhead=4, dim_feedforward=64,
                                   dropout=0.0), num_layers=2)
    head = nn.Linear(32, 2)
    opt = optimizer.Adam(1e-3, parameters=list(enc.parameters())
                         + list(head.parameters()))
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 12, 32).astype("float32"))
    y = paddle.to_tensor((rng.rand(16) > 0.5).astype("int64"))
    losses = []
    for _ in range(steps):
        with paddle.amp.auto_cast(enable=True, level="O1"):
            loss = nn.functional.cross_entropy(head(enc(x).mean(axis=1)), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


def test_bf16_softmax_numerics_close_on_f32_inputs():
    # on f32 inputs the flag's branch keeps f32 end-to-end: identical
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 16, 8), jnp.float32)
    a = attention._sdpa_ref(q, q, q, None, None, scale=0.35, dropout_p=0.0,
                            is_causal=False, fp32_softmax=True)
    b = attention._sdpa_ref(q, q, q, None, None, scale=0.35, dropout_p=0.0,
                            is_causal=False, fp32_softmax=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bf16_softmax_close_on_bf16_inputs():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.bfloat16)
    a = attention._sdpa_ref(q, q, q, None, None, scale=0.35, dropout_p=0.0,
                            is_causal=False, fp32_softmax=True)
    b = attention._sdpa_ref(q, q, q, None, None, scale=0.35, dropout_p=0.0,
                            is_causal=False, fp32_softmax=False)
    assert a.dtype == b.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_training_converges_either_way():
    base = _train(True)
    fast = _train(False)
    assert base[-1] < base[0] * 0.5, base
    assert fast[-1] < fast[0] * 0.5, fast
