"""The gated delta rule with a decay per key channel
(paddle_tpu/ops/linear_attention.py): the chunked scan against the
recurrence over tokens — outputs, the final state and the gradients with
respect to q, k, v, g and beta — at lengths that are no multiple of the
chunk, across segments, from a given state, and with a decay strong enough
to overflow a factorisation into exp(G_r) exp(-G_i); the operand dtype
under amp O1; which path a row takes and the counter that says so; and the
causal depthwise convolution against four shifted multiply-adds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import linear_attention as la

HEADS, D_K, D_V = 3, 32, 16


def inputs(seed, batch, seq, strong=False, dtype=jnp.float32):
    """q, k as a KDA layer makes them (L2-normalised, q scaled), v, a decay
    g <= 0 per channel and beta in (0, 1). ``strong``: |g| up to e^5 = 148 a
    token, so exp(-G) over a 16-token sub-block alone reaches e^2000."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (batch, seq, HEADS, D_K))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(ks[0]) * D_K ** -0.5, unit(ks[1])
    v = jax.random.normal(ks[2], (batch, seq, HEADS, D_V))
    g = -jnp.exp(jax.random.uniform(ks[3], (batch, seq, HEADS, D_K),
                                    minval=-6.0,
                                    maxval=5.0 if strong else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, HEADS)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.fixture(autouse=True)
def _float32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def close(got, want, rtol):
    scale = float(jnp.abs(want).max())
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= rtol * scale, (err, scale)


# float32 both ways; the chunked form sums in another order and through an
# inverse. A strong decay costs digits in G's running sum (|G| reaches
# thousands, its differences are O(1)): 2e-5 of the scale read there.
@pytest.mark.parametrize("seq, chunk, segment, strong, rtol", [
    (100, 64, 2048, False, 2e-6),     # ragged: one chunk and a part
    (256, 64, 128, False, 2e-6),      # two segments of two chunks
    (300, 64, 128, False, 2e-6),      # ragged across three segments
    (70, 16, 32, False, 2e-6),        # one sub-block a chunk
    (48, 32, 2048, False, 2e-6),      # two sub-blocks a chunk
    (100, 64, 2048, True, 1e-4),
    (300, 64, 128, True, 1e-4),
    (257, 128, 256, True, 1e-4),      # eight sub-blocks a chunk
])
def test_chunked_scan_is_the_recurrence(seq, chunk, segment, strong, rtol):
    args = inputs(0, 2, seq, strong)
    want_o, want_s = la.kda_recurrent(*args)
    got_o, got_s = jax.jit(lambda *a: la.kda_chunked(
        *a, chunk=chunk, segment=segment))(*args)
    assert got_o.shape == want_o.shape == (2, seq, HEADS, D_V)
    assert got_s.shape == want_s.shape == (2, HEADS, D_K, D_V)
    assert got_s.dtype == jnp.float32
    close(got_o, want_o, rtol)
    close(got_s, want_s, rtol)


def test_a_naive_factorisation_would_overflow_where_the_scan_does_not():
    """The strong decay of these tests is strong enough: exp(-G) over one
    chunk is inf in float32, so q exp(G) times k exp(-G) would be nan."""
    _, _, _, g, _ = inputs(0, 2, 100, strong=True)
    cum = jnp.cumsum(g[:, :64], axis=1)
    assert bool(jnp.isinf(jnp.exp(-cum)).any())
    assert float(cum.min()) < -1000


@pytest.mark.parametrize("seq, strong, rtol", [
    (100, False, 2e-5), (300, False, 2e-5), (100, True, 5e-4)])
def test_gradients_with_respect_to_q_k_v_g_and_beta(seq, strong, rtol):
    args = inputs(1, 2, seq, strong)

    def loss(fn):
        def of(*a):
            o, s = fn(*a)
            # weights that tell positions and features apart
            return (jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
                    + jnp.sum(s * jnp.sin(jnp.arange(s.size).reshape(
                        s.shape))))
        return jax.jit(jax.grad(of, argnums=(0, 1, 2, 3, 4)))

    want = loss(la.kda_recurrent)(*args)
    got = loss(lambda *a: la.kda_chunked(*a, chunk=64, segment=128))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        close(a, b, rtol)


def test_a_given_state_carries_on_and_takes_a_gradient():
    """Two halves, the second from the first's state, are the whole row."""
    args = inputs(2, 1, 160)
    whole_o, whole_s = la.kda_chunked(*args, chunk=32, segment=64)
    first = tuple(a[:, :90] for a in args)
    second = tuple(a[:, 90:] for a in args)
    o1, s1 = la.kda_chunked(*first, chunk=32, segment=64)
    o2, s2 = la.kda_chunked(*second, initial_state=s1, chunk=32, segment=64)
    close(jnp.concatenate([o1, o2], axis=1), whole_o, 2e-6)
    close(s2, whole_s, 2e-6)
    want = jax.grad(lambda s: jnp.sum(la.kda_recurrent(
        *second, initial_state=s)[0] ** 2))(s1)
    got = jax.grad(lambda s: jnp.sum(la.kda_chunked(
        *second, initial_state=s, chunk=32, segment=64)[0] ** 2))(s1)
    close(got, want, 2e-5)


def test_bf16_operands_keep_the_state_and_the_decay_in_float32():
    """Under amp O1 a layer hands over bf16 q, k, v and float32 g, beta:
    the large products take bf16 operands (the jaxpr holds them), the
    output takes v's dtype, the state stays float32, and the result is a
    bf16 rounding off the float32 one, not more."""
    args32 = inputs(3, 1, 200)
    args16 = inputs(3, 1, 200, dtype=jnp.bfloat16)
    o32, s32 = la.kda_chunked(*args32, chunk=64)
    o16, s16 = la.kda_chunked(*args16, chunk=64)
    assert o16.dtype == jnp.bfloat16 and s16.dtype == jnp.float32
    assert 1e-4 < float(jnp.abs(o16.astype(jnp.float32) - o32).max()) / float(
        jnp.abs(o32).max()) < 3e-2
    close(s16, s32, 3e-2)
    jaxpr = str(jax.make_jaxpr(lambda *a: la.kda_chunked(*a, chunk=64))(
        *args16))
    assert "bf16" in jaxpr and "preferred_element_type=float32" in jaxpr
    # g and beta are never rounded: the decay sums are float32
    assert "f32[1,3,4,64,32]" in jaxpr and "bf16[1,3,4,64,32]" in jaxpr


def test_chunk_must_be_a_power_of_two_of_sub_blocks():
    args = inputs(4, 1, 64)
    with pytest.raises(ValueError, match="power of two"):
        la.kda_chunked(*args, chunk=48)


@pytest.mark.parametrize("seq, path", [(8, "recurrent"), (15, "recurrent"),
                                       (16, "chunked"), (100, "chunked")])
def test_the_entry_point_picks_and_counts_the_path(seq, path):
    assert la.core_path(seq) == path
    other = "recurrent" if path == "chunked" else "chunked"
    before = {p: la._CORE_TOTAL.value(path=p) for p in (path, other)}
    args = inputs(5, 1, seq)
    out = la.gated_delta_rule(*(paddle.to_tensor(np.asarray(a))
                                for a in args), chunk=16)
    assert la._CORE_TOTAL.value(path=path) == before[path] + 1
    assert la._CORE_TOTAL.value(path=other) == before[other]
    close(out._value, la.kda_recurrent(*args)[0], 2e-6)


def test_the_core_differentiates_inside_jax_checkpoint():
    args = inputs(6, 1, 96)

    def loss(*a):
        return jnp.sum(la.kda_chunked(*a, chunk=32, segment=64)[0] ** 2)

    want = jax.grad(loss, argnums=(0, 3))(*args)
    got = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 3)))(*args)
    for a, b in zip(got, want):
        close(a, b, 1e-6)


# ------------------------------------------------ the short convolution
def shifted_multiply_adds(x, w):
    """y_t = w[3] x_t + w[2] x_{t-1} + w[1] x_{t-2} + w[0] x_{t-3}, with
    nothing before a row's start."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for back in range(w.shape[0]):
        tap = w[w.shape[0] - 1 - back]
        out[:, back:] += x[:, :x.shape[1] - back] * tap
    return out


@pytest.mark.parametrize("activation", [None, "silu"])
def test_causal_depthwise_convolution_is_four_shifted_multiply_adds(
        activation):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 11, 6)).astype(np.float32)
    w = rng.standard_normal((4, 6)).astype(np.float32)
    want = shifted_multiply_adds(x, w)
    if activation:
        want = want / (1.0 + np.exp(-want))
    got = F.causal_depthwise_conv1d(paddle.to_tensor(x), paddle.to_tensor(w),
                                    activation)
    np.testing.assert_allclose(np.asarray(got._value), want, rtol=1e-5,
                                atol=1e-6)
    # causal: a later position moves no earlier output; per channel: one
    # channel's input moves no other channel's output
    x2 = x.copy()
    x2[:, 7:, :] += 1.0
    x2[:, :, 3] += 1.0
    got2 = np.asarray(F.causal_depthwise_conv1d(
        paddle.to_tensor(x2), paddle.to_tensor(w), activation)._value)
    keep = [c for c in range(6) if c != 3]
    np.testing.assert_array_equal(got2[:, :7][..., keep],
                                  np.asarray(got._value)[:, :7][..., keep])


def test_the_convolution_layer_and_its_gradient():
    paddle.seed(0)
    conv = nn.CausalDepthwiseConv1D(8, 4, activation="silu")
    assert conv.weight.shape == [4, 8]
    assert float(np.abs(np.asarray(conv.weight._value)).max()) <= 0.5
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 9, 8)).astype(np.float32), stop_gradient=False)
    out = conv(x)
    out.sum().backward()
    assert out.shape == [2, 9, 8]
    w = np.asarray(conv.weight._value)

    def fn(xv, wv):
        pre = sum(jnp.pad(xv, ((0, 0), (3 - j, 0), (0, 0)))[:, :9] * wv[j]
                  for j in range(4))
        return jnp.sum(jax.nn.silu(pre))

    gx, gw = jax.grad(fn, argnums=(0, 1))(x._value, jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(x.grad._value), gx, rtol=1e-5,
                                atol=1e-6)
    np.testing.assert_allclose(np.asarray(conv.weight.grad._value), gw,
                                rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="activation"):
        F.causal_depthwise_conv1d(x, conv.weight, "relu")


def test_bf16_input_convolves_in_float32_and_returns_bf16():
    x = paddle.to_tensor(np.ones((1, 5, 4), np.float32)).astype("bfloat16")
    w = paddle.to_tensor(np.full((4, 4), 1.0 / 3.0, np.float32))
    out = F.causal_depthwise_conv1d(x, w)
    assert str(out.dtype).endswith("bfloat16")
    np.testing.assert_allclose(
        np.asarray(out._value.astype(jnp.float32))[0, :, 0],
        [1 / 3, 2 / 3, 1.0, 4 / 3, 4 / 3], rtol=1e-2)
