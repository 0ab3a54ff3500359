"""The gated delta rule with a decay per key channel
(paddle_tpu/ops/linear_attention.py): the chunked scan against the
recurrence over tokens — outputs, the final state and the gradients with
respect to q, k, v, g and beta — at lengths that are no multiple of the
chunk, across segments, from a given state, and with a decay strong enough
to overflow a factorisation into exp(G_r) exp(-G_i); the operand dtype
under amp O1; the Mosaic kernels (``ops/pallas/linear_attention.py``, in
the Pallas interpreter) against the recurrence and against the chunked scan,
outputs and all five gradients; which path a row takes and the counter that
says so; and the causal depthwise convolution against four shifted
multiply-adds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops import placement
from paddle_tpu.ops.pallas import linear_attention as kernels

HEADS, D_K, D_V = 3, 32, 16


def inputs(seed, batch, seq, strong=False, dtype=jnp.float32, heads=HEADS,
           d_k=D_K, d_v=D_V):
    """q, k as a KDA layer makes them (L2-normalised, q scaled), v, a decay
    g <= 0 per channel and beta in (0, 1). ``strong``: |g| up to e^5 = 148 a
    token, so exp(-G) over a 16-token sub-block alone reaches e^2000."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (batch, seq, heads, d_k))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(ks[0]) * d_k ** -0.5, unit(ks[1])
    v = jax.random.normal(ks[2], (batch, seq, heads, d_v))
    g = -jnp.exp(jax.random.uniform(ks[3], (batch, seq, heads, d_k),
                                    minval=-6.0,
                                    maxval=5.0 if strong else 0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.fixture(autouse=True)
def _float32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def close(got, want, rtol):
    got, want = jnp.asarray(got), jnp.asarray(want)
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


# ------------------------------------------------ the Mosaic kernels
# In the Pallas interpreter, at the one width they take (128 lanes), two
# heads. Float32 both ways the kernels are the chunked scan's arithmetic in
# another order: the tolerances are the chunked tests' own.
def kernel_inputs(seed, seq, strong=False, dtype=jnp.float32):
    return inputs(seed, 1, seq, strong, dtype, heads=2, d_k=128, d_v=128)


def weighted(fn):
    """value and the five gradients of a loss that tells positions and
    features apart."""
    def of(*a):
        o = fn(*a).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                                   .reshape(o.shape)))
    return jax.jit(jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4)))


def kernel_scan(*a, tokens=None, together=None):
    return kernels.kda(*a, tokens=tokens, together=together, interpret=True)


@pytest.mark.parametrize("seq, tokens, together, strong, against, rtol", [
    (200, 128, 2, False, "recurrent", 2e-5),   # two programs a row, ragged
    (100, None, 2, False, "recurrent", 2e-5),  # one chunk and a part
    (200, 128, 2, True, "recurrent", 5e-4),    # exp(-G) overflows in a chunk
    (64, None, 2, False, "recurrent", 2e-5),   # the shortest row routed here
    (200, 128, 1, False, "chunked", 2e-5),     # a head a program
    (200, 256, 2, True, "chunked", 5e-4),
])
def test_the_kernels_are_the_scan_forward_and_backward(
        seq, tokens, together, strong, against, rtol):
    args = kernel_inputs(7, seq, strong)
    other = {"recurrent": lambda *a: la.kda_recurrent(*a)[0],
             "chunked": lambda *a: la.kda_chunked(*a)[0]}[against]
    want_o = other(*args)
    def scan(*a):
        return kernel_scan(*a, tokens=tokens, together=together)

    got_o = jax.jit(scan)(*args)
    assert got_o.shape == want_o.shape and got_o.dtype == want_o.dtype
    close(got_o, want_o, rtol / 10 if not strong else rtol / 5)
    _, want = weighted(other)(*args)
    _, got = weighted(scan)(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        assert a.shape == b.shape and a.dtype == b.dtype, name
        close(a, b, rtol)


def test_the_kernels_take_bf16_operands_and_keep_float32_inside():
    """bf16 q, k, v, float32 decay and beta, as amp O1 hands them over: the
    output and dq, dk, dv take bf16, dg and dbeta float32, and all of them
    are a bf16 rounding off the float32 recurrence on the same (rounded)
    inputs; the kept states and matrices are float32."""
    args = kernel_inputs(8, 200, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    want_o, want = (la.kda_recurrent(*exact)[0],
                    weighted(lambda *a: la.kda_recurrent(*a)[0])(*exact)[1])
    got_o = kernel_scan(*args)
    _, got = weighted(kernel_scan)(*args)
    assert got_o.dtype == jnp.bfloat16
    close(got_o, want_o, 3e-2)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        close(a, b, 3e-2)
    blocked = kernels._blocked(*args, 128)
    _, s0, pair, inv = kernels._forward(*blocked, tokens=128, together=2,
                                        keep=True, interpret=True)
    assert {x.dtype for x in (s0, pair, inv)} == {jnp.dtype(jnp.float32)}
    assert s0.shape == (1, 2, 4, 128, 128)
    jaxpr = str(jax.make_jaxpr(kernel_scan)(*args))
    assert "bf16" in jaxpr and "preferred_element_type=float32" in jaxpr


def test_no_exponent_in_the_kernels_is_positive():
    """Every ``exp`` the kernels' bodies take — a block's set-up, which both
    run, and the pair terms' gradient — on a decay that overflows a
    factorisation: its argument is never above zero."""
    q, k, v, g, beta = kernel_inputs(9, 128, strong=True)

    def chunks(x):
        return x[0, :, 0].reshape(2, kernels.CHUNK, -1)

    seen = []
    real = jnp.exp

    def exp(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    try:
        jnp.exp = exp
        s = kernels._set_up(chunks(q), chunks(k), chunks(v), chunks(g),
                            beta[0, :, 0].reshape(2, 1, kernels.CHUNK))
        kernels._pair_terms_bwd(s["qf"], s["kf"], s["cum"], s["a_kk"],
                                s["a_qk"])
    finally:
        jnp.exp = real
    assert len(seen) > 2 * kernels.SUB and max(seen) <= 0.0
    assert float(s["cum"].min()) < -1000       # exp(-G) would be inf


@pytest.fixture
def interpreter():
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags({"pallas_interpret": False})


@pytest.mark.parametrize("seq, path", [(8, "recurrent"), (15, "recurrent"),
                                       (16, "chunked"), (100, "chunked")])
def test_the_entry_point_picks_and_counts_the_path(seq, path):
    assert la.core_path(seq) == path
    assert la.core_path(seq, D_K, D_V, jnp.float32) == path
    other = "recurrent" if path == "chunked" else "chunked"
    before = {p: la._CORE_TOTAL.value(path=p) for p in (path, other)}
    args = inputs(5, 1, seq)
    out = la.gated_delta_rule(*(paddle.to_tensor(np.asarray(a))
                                for a in args), chunk=16)
    assert la._CORE_TOTAL.value(path=path) == before[path] + 1
    assert la._CORE_TOTAL.value(path=other) == before[other]
    close(out._value, la.kda_recurrent(*args)[0], 2e-6)


@pytest.mark.parametrize("seq, d_k, d_v, dtype, path", [
    (15, 128, 128, jnp.bfloat16, "recurrent"),
    (63, 128, 128, jnp.bfloat16, "chunked"),      # under one chunk
    (64, 128, 128, jnp.bfloat16, "kernel"),
    (16384, 128, 128, jnp.float32, "kernel"),
    (16384, 256, 256, jnp.bfloat16, "kernel"),
    (16384, 64, 64, jnp.bfloat16, "chunked"),     # half a lane group
    (16384, 192, 128, jnp.bfloat16, "chunked"),   # two widths
    (16384, 128, 128, jnp.float16, "chunked"),
])
def test_the_route_goes_by_widths_dtype_and_length(interpreter, seq, d_k,
                                                   d_v, dtype, path):
    assert la.core_path(seq, d_k, d_v, dtype) == path


def test_the_route_needs_a_platform_and_known_devices(monkeypatch):
    """No TPU and no interpreter flag: the XLA scan. A platform that
    compiles the kernels but a program whose devices are not known (a plain
    jit on several devices): the XLA scan again."""
    shape = (16384, 128, 128, jnp.bfloat16)
    assert la.core_path(*shape) == "chunked"
    monkeypatch.setattr(placement, "is_tpu_available", lambda: True)
    assert jax.device_count() > 1                       # conftest's mesh
    assert la.core_path(*shape) == "chunked"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert la.core_path(*shape) == "kernel"


@pytest.mark.parametrize("seq, d, path", [(150, 128, "kernel"),
                                          (150, 32, "chunked"),
                                          (40, 128, "chunked"),
                                          (12, 128, "recurrent")])
def test_the_entry_point_counts_the_kernel_path(interpreter, seq, d, path):
    before = {p: la._CORE_TOTAL.value(path=p)
              for p in ("kernel", "chunked", "recurrent")}
    args = inputs(10, 1, seq, heads=2, d_k=d, d_v=d)
    out = la.gated_delta_rule(*(paddle.to_tensor(np.asarray(a))
                                for a in args))
    for p, n in before.items():
        assert la._CORE_TOTAL.value(path=p) == n + (p == path), p
    close(out._value, la.kda_recurrent(*args)[0], 2e-6)


def test_the_kernel_path_differentiates_through_the_tape(interpreter):
    """The layer's call: Tensors in, ``backward`` through the eager tape,
    the gradients those of the chunked scan."""
    args = kernel_inputs(11, 130)

    def run(fn):
        ts = [paddle.to_tensor(np.asarray(a), stop_gradient=False)
              for a in args]
        (fn(*ts) ** 2).sum().backward()
        return [t.grad._value for t in ts]

    got = run(la.gated_delta_rule)
    want = jax.grad(lambda *a: jnp.sum(la.kda_chunked(*a)[0] ** 2),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        close(a, b, 2e-5)


def test_the_kernel_path_shards_itself_over_an_announced_mesh(interpreter):
    """Inside a step traced for a mesh (``topology.tracing_for``) the call
    runs under the attention kernels' ``shard_map``: rows over the data
    axis, heads over 'mp', nothing replicated; the result is the one-device
    one and the program holds a shard_map."""
    from paddle_tpu.distributed import topology

    q, k, v, g, beta = (jnp.concatenate([a, a[:, ::-1]], axis=0)
                        for a in kernel_inputs(12, 128))
    args = tuple(paddle.to_tensor(np.asarray(a)) for a in (q, k, v, g, beta))
    want = la.gated_delta_rule(*args)._value
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    def step(*a):
        with topology.tracing_for(mesh):
            return la._kernel_output(*a, interpret=True)

    text = jax.jit(step).lower(q, k, v, g, beta).as_text()
    assert "shard_map" in text or "manual" in text
    close(np.asarray(jax.jit(step)(q, k, v, g, beta)), np.asarray(want),
          1e-6)


@pytest.mark.parametrize("mode", [[], ["--layer", "gdn"]])
def test_the_kernel_microbenchmark_measures_on_a_tpu_only(mode):
    """``tools/kda_kernel_bench.py`` exits 2 where there is no TPU, in its
    scan mode and in its ``--layer`` mode: a number from this CPU is never
    printed under a device's name."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "kda_kernel_bench.py"),
         *mode],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 2 and "TPU only" in done.stderr
    assert "fwd_ms" not in done.stdout


@pytest.mark.parametrize("mode", [[], ["--stage", "conv"], ["--path"]])
def test_the_scan_microbenchmark_measures_on_a_tpu_only(mode):
    """``tools/ssd_bench.py`` — the state-space scan by chunk and segment,
    with ``--stage conv`` the biased convolution stage on both of its
    paths, and with ``--path`` the scan on both of its own (the Mosaic
    kernels against the XLA scan) — exits 2 where there is no TPU, as the
    delta rule's does."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "ssd_bench.py"), *mode],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 2 and "no TPU" in done.stderr
    assert "fwd_ms" not in done.stdout


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


# ------------------------------------------------- one decay a head (GDN)
# Gated DeltaNet's decay is one number a head and token, [B, T, H]: every
# path takes it as it is (its pair terms factor), and each is held to the
# recurrence, to the others, and to the per-channel path fed the same decay
# broadcast over the key channels — outputs and all five gradients, the
# decay's summed over the channels it was broadcast to.
def scalar_inputs(seed, batch, seq, strong=False, dtype=jnp.float32, **kw):
    q, k, v, g, beta = inputs(seed, batch, seq, strong, dtype, **kw)
    return q, k, v, g[..., 0], beta


def broadcast(args):
    q, k, v, g, beta = args
    return q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta


@pytest.mark.parametrize("seq, chunk, segment, strong, rtol", [
    (100, 64, 2048, False, 2e-6),
    (300, 64, 128, False, 2e-6),
    (70, 16, 32, False, 2e-6),
    (300, 64, 128, True, 1e-4),
])
def test_scalar_decay_chunked_scan_is_the_recurrence(seq, chunk, segment,
                                                     strong, rtol):
    args = scalar_inputs(0, 2, seq, strong)
    want_o, want_s = la.kda_recurrent(*args)
    # the recurrence itself takes either form of the decay
    wide_o, wide_s = la.kda_recurrent(*broadcast(args))
    close(want_o, wide_o, 1e-6)
    close(want_s, wide_s, 1e-6)
    got_o, got_s = jax.jit(lambda *a: la.kda_chunked(
        *a, chunk=chunk, segment=segment))(*args)
    assert got_o.shape == want_o.shape == (2, seq, HEADS, D_V)
    close(got_o, want_o, rtol)
    close(got_s, want_s, rtol)


@pytest.mark.parametrize("path", ["recurrent", "chunked"])
def test_scalar_decay_gradients_are_the_per_channel_paths(path):
    fn = {"recurrent": lambda *a: la.kda_recurrent(*a)[0],
          "chunked": lambda *a: la.kda_chunked(*a, chunk=16, segment=64)[0]}
    args = scalar_inputs(3, 2, 150)
    value, got = weighted(fn[path])(*args)
    want_value, want = weighted(fn["recurrent"])(*broadcast(args))
    assert float(value) == pytest.approx(float(want_value), rel=2e-5)
    for name, a, b in zip("q k v g beta".split(), got, want):
        b = b.sum(-1) if name == "g" else b
        assert a.shape == b.shape and float(jnp.abs(b).max()) > 0, name
        close(a, b, 2e-5)


@pytest.mark.parametrize("seq, tokens, together, strong, against, rtol", [
    (200, 128, 2, False, "recurrent", 2e-5),   # two programs a row, ragged
    (64, None, 2, False, "recurrent", 2e-5),   # the shortest row routed here
    (200, 128, 2, True, "recurrent", 5e-4),    # exp(-G) overflows in a chunk
    (200, 256, 1, False, "chunked", 2e-5),     # a head a program
    (200, 128, 2, False, "per-channel kernel", 2e-5),
])
def test_the_kernels_take_a_decay_a_head_forward_and_backward(
        seq, tokens, together, strong, against, rtol):
    args = scalar_inputs(7, 1, seq, strong, heads=2, d_k=128, d_v=128)

    def scan(*a):
        return kernel_scan(*a, tokens=tokens, together=together)

    other, theirs = {
        "recurrent": (lambda *a: la.kda_recurrent(*a)[0], args),
        "chunked": (lambda *a: la.kda_chunked(*a)[0], args),
        "per-channel kernel": (scan, broadcast(args))}[against]
    want_o = other(*theirs)
    got_o = jax.jit(scan)(*args)
    assert got_o.shape == want_o.shape and got_o.dtype == want_o.dtype
    close(got_o, want_o, rtol / 10 if not strong else rtol / 5)
    _, want = weighted(other)(*theirs)
    _, got = weighted(scan)(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        if name == "g" and b.ndim == 4:
            b = b.sum(-1)
        # dg leaves as [B, T, H]: no [.., d_k] decay on the way back either
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.abs(b).max()) > 0, name
        close(a, b, rtol)


def test_the_scalar_kernels_take_bf16_operands():
    """bf16 q, k, v, float32 decay a head and beta, as amp O1 hands them
    over: the factored pair terms are bf16 products summed in float32, and
    the outputs stay as near the float32 recurrence as the chunked scan's
    do."""
    args = scalar_inputs(9, 1, 200, dtype=jnp.bfloat16, heads=2, d_k=128,
                         d_v=128)
    exact = la.kda_recurrent(*(a.astype(jnp.float32) for a in args))[0]
    got = jax.jit(kernel_scan)(*args)
    assert got.dtype == jnp.bfloat16
    close(got, exact, 2e-2)
    _, grads = weighted(kernel_scan)(*args)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert grads[3].shape == args[3].shape


@pytest.mark.parametrize("seq, d, path", [(150, 128, "kernel_scalar"),
                                          (150, 32, "chunked_scalar"),
                                          (12, 128, "recurrent_scalar")])
def test_the_entry_point_counts_the_decay_with_the_path(interpreter, seq, d,
                                                        path):
    """``gated_delta_rule`` with a [B, T, H] decay: the same route, counted
    under ``<path>_scalar``, so that the per-channel counts stay what they
    were."""
    names = [p + s for p in ("kernel", "chunked", "recurrent")
             for s in ("", "_scalar")]
    before = {p: la._CORE_TOTAL.value(path=p) for p in names}
    args = scalar_inputs(10, 1, seq, heads=2, d_k=d, d_v=d)
    out = la.gated_delta_rule(*(paddle.to_tensor(np.asarray(a))
                                for a in args))
    for p, n in before.items():
        assert la._CORE_TOTAL.value(path=p) == n + (p == path), p
    close(out._value, la.kda_recurrent(*args)[0], 2e-6)


# ------------------------------------------- streams: the kernels' tiling
# A layer that keeps its stages on [B, T, H d] hands the scan streams and
# gets a stream back; H is beta's. Every path is held to its own rank-4
# entry, outputs and all five gradients: the chunked scan and the
# recurrence reshape inside, the kernels take the streams as they are.
def as_streams(args):
    """q, k, v (and a per-channel decay) [B, T, H, d] -> [B, T, H d]."""
    *wide, beta = args
    return tuple(a.reshape(*a.shape[:2], -1) if a.ndim == 4 else a
                 for a in wide) + (beta,)


def entry_output(path, interpret):
    fn = {"recurrent": la._recurrent_output,
          "chunked": lambda *a: la._chunked_output(*a, chunk=16),
          "kernel": lambda *a: la._kernel_output(*a, interpret=interpret)}
    return fn[path]


@pytest.mark.parametrize("path, seq, scalar", [
    ("recurrent", 12, False), ("recurrent", 12, True),
    ("chunked", 100, False), ("chunked", 100, True),
    ("kernel", 256, False), ("kernel", 256, True),
    ("kernel", 200, False),        # a row ``_blocked`` pads to whole blocks
    ("kernel", 200, True),
])
def test_the_stream_entry_is_the_heads_entry(path, seq, scalar):
    d = 128 if path == "kernel" else D_K
    args = (scalar_inputs if scalar else inputs)(
        13, 1, seq, heads=2, d_k=d, d_v=d)
    fn = entry_output(path, interpret=True)
    _, want = weighted(fn)(*args)
    _, got = weighted(fn)(*as_streams(args))
    got_o = jax.jit(fn)(*as_streams(args))
    assert got_o.shape == (1, seq, 2 * d)      # a stream in, a stream out
    close(got_o.reshape(1, seq, 2, d), jax.jit(fn)(*args), 1e-6)
    for name, a, b in zip("q k v g beta".split(), got, as_streams(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.abs(b).max()) > 0, name
        close(a, b, 1e-5)


@pytest.mark.parametrize("seq, d, path, scalar, entry", [
    (150, 128, "kernel", False, "streams"),
    (150, 128, "kernel", False, "heads"),
    (150, 128, "kernel", True, "streams"),
    (150, 32, "chunked", False, "streams"),
    (150, 32, "chunked", True, "heads"),
    (12, 128, "recurrent", True, "streams"),
])
def test_the_entry_point_counts_the_entry_with_the_path(
        interpreter, seq, d, path, scalar, entry):
    """``paddle_tpu_kda_core_entry_total{path, entry}``: ``streams`` for
    [B, T, H d] arguments, ``heads`` for [B, T, H, d] — one count a call,
    beside ``paddle_tpu_kda_core_total{path}``'s, which stays what it was;
    o comes back in the rank given."""
    path += "_scalar" if scalar else ""
    names = [(p + s, e) for p in ("kernel", "chunked", "recurrent")
             for s in ("", "_scalar") for e in ("streams", "heads")]
    before = {n: la._ENTRY_TOTAL.value(path=n[0], entry=n[1]) for n in names}
    paths = {n[0]: la._CORE_TOTAL.value(path=n[0]) for n in names}
    args = (scalar_inputs if scalar else inputs)(
        10, 1, seq, heads=2, d_k=d, d_v=d)
    want = la.kda_recurrent(*args)[0]
    if entry == "streams":
        args, want = as_streams(args), want.reshape(1, seq, -1)
    out = la.gated_delta_rule(*(paddle.to_tensor(np.asarray(a))
                                for a in args))
    for n, count in before.items():
        assert la._ENTRY_TOTAL.value(path=n[0], entry=n[1]) == count + (
            n == (path, entry)), n
    for p, count in paths.items():
        assert la._CORE_TOTAL.value(path=p) == count + (p == path), p
    assert out.shape == list(want.shape)
    close(out._value, want, 2e-6)


def test_streams_shard_their_heads_over_an_announced_mesh(interpreter):
    """Streams under ``placement.on_mesh``: dim 2 holds heads x d and shards over
    'mp' where the HEADS divide (a head stays a contiguous slice); three
    heads on mp = 2 are computed whole on both devices, not cut through a
    head."""
    from paddle_tpu.distributed import topology

    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    for heads, sharded in ((2, True), (3, False)):
        args = tuple(jnp.concatenate([a, a[:, ::-1]], axis=0)
                     for a in inputs(12, 1, 128, heads=heads, d_k=128,
                                     d_v=128))
        want = la._kernel_output(*args, interpret=True)

        def step(*a):
            with topology.tracing_for(mesh):
                return la._kernel_output(*a, interpret=True)

        streams = as_streams(args)
        text = jax.jit(step).lower(*streams).as_text()
        assert "shard_map" in text or "manual" in text
        got = jax.jit(step)(*streams)
        assert got.shape == (2, 128, heads * 128)
        close(got.reshape(want.shape), want, 1e-6)
        specs = [str(s) for s in _shard_map_in_specs(step, streams)]
        assert any("mp" in s for s in specs) == sharded, specs


def _shard_map_in_specs(fn, args):
    """The in_specs (as jaxpr params) of the first shard_map ``fn`` traces."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                return eqn.params["in_specs"]
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                found = find(inner) if hasattr(inner, "eqns") else None
                if found is not None:
                    return found
        return None

    return find(jaxpr.jaxpr)


# ------------------------------------------------ the convolution stage
# ``ops.linear_attention.conv_streams``: the Mosaic kernels (in the Pallas
# interpreter) against the XLA stage — what every other program runs —
# forward and the stated VJP, in Kimi Delta Attention's form (three
# streams, a segment each) and in Gated DeltaNet's (one stream, three
# segments); which path a stage takes, and the counter that says so.
CONV_D = 128


def conv_form(form, seed, dtype, taps, batch=2, seq=80):
    """(xs, ws, segments) of a stage with heads of 128: ``kda`` three
    streams of two heads, q and k normed and v not; ``gdn`` one stream of
    q | k | v with 2 | 2 | 4 heads, the published 2,048 | 2,048 | 4,096
    scaled down."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def normal(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    d = CONV_D
    if form == "kda":
        wide = 2 * d
        xs = tuple(normal(batch, seq, wide).astype(dtype) for _ in range(3))
        ws = tuple(normal(taps, wide, scale=0.5) for _ in range(3))
        segments = ((0, 0, wide, d ** -0.5), (1, 0, wide, 1.0),
                    (2, 0, wide, None))
    else:
        xs = (normal(batch, seq, 8 * d).astype(dtype),)
        ws = (normal(taps, 8 * d, scale=0.5),)
        segments = ((0, 0, 2 * d, d ** -0.5), (0, 2 * d, 2 * d, 1.0),
                    (0, 4 * d, 4 * d, None))
    return xs, ws, segments


def conv_kernels(xs, ws, segments, tokens=32, lanes=128, biases=None,
                 head=CONV_D):
    """The kernels as the op calls them (the taps a copy a batch row, a
    stream's bias the copy's last row), at blocks small enough that a row is
    several of them and a segment several channel steps."""
    if biases is not None:
        ws = tuple(jnp.concatenate([w, b[None]]) for w, b in zip(ws, biases))
    rows = tuple(jnp.broadcast_to(w[None], (xs[0].shape[0],) + w.shape)
                 for w in ws)
    return kernels.conv_streams(xs, rows, segments, head=head, eps=1e-6,
                                bias=biases is not None, tokens=tokens,
                                lanes=lanes, interpret=True)


@pytest.mark.parametrize("activation", [None, "silu"])
def test_a_bias_joins_the_taps_sum_before_the_activation(activation):
    """Mamba-2's convolution: one number a channel on four shifted
    multiply-adds, then SiLU — at a row's first token too, on a zero
    history; through the functional and the layer, with its gradient."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 11, 6)).astype(np.float32)
    w = rng.standard_normal((4, 6)).astype(np.float32)
    b = rng.standard_normal(6).astype(np.float32)
    want = shifted_multiply_adds(x, w) + b
    if activation:
        want = want / (1.0 + np.exp(-want))
    got = F.causal_depthwise_conv1d(
        paddle.to_tensor(x), paddle.to_tensor(w), activation,
        bias=paddle.to_tensor(b))
    np.testing.assert_allclose(np.asarray(got._value), want, rtol=1e-5,
                               atol=1e-6)
    paddle.seed(0)
    conv = nn.CausalDepthwiseConv1D(6, 4, activation=activation, bias=True)
    assert conv.bias.shape == [6]
    # taps and bias start as torch's depthwise Conv1d starts them
    assert 0 < float(np.abs(np.asarray(conv.bias._value)).max()) <= 0.5
    assert nn.CausalDepthwiseConv1D(6, 4).bias is None
    conv.weight.set_value(w)
    conv.bias.set_value(b)
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = conv(xt)
    np.testing.assert_allclose(np.asarray(out._value), want, rtol=1e-5,
                               atol=1e-6)
    out.sum().backward()

    def fn(bv):
        pre = sum(jnp.pad(x, ((0, 0), (3 - j, 0), (0, 0)))[:, :11] * w[j]
                  for j in range(4)) + bv
        return jnp.sum(jax.nn.silu(pre) if activation else pre)

    np.testing.assert_allclose(np.asarray(conv.bias.grad._value),
                               jax.grad(fn)(jnp.asarray(b)), rtol=1e-5,
                               atol=1e-5)


def _lowered(fn, *args):
    """``fn``'s lowered text less the module's name."""
    text = jax.jit(fn).lower(*args).as_text()
    return text.split("\n", 1)[1]


def test_a_convolution_without_a_bias_lowers_to_what_it_did():
    """The functional's body as it stood before there was a bias (PR 46),
    written out here: a call without one lowers to that text, on the
    functional and through ``conv_streams``' XLA stage; a call with one
    does not."""
    def before(x, w, *, activation):
        k = w.shape[0]
        xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
        padded = jnp.pad(xf, ((0, 0), (k - 1, 0), (0, 0)))
        seq = x.shape[1]
        out = sum(padded[:, j:j + seq] * wf[j] for j in range(k))
        if activation == "silu":
            out = jax.nn.silu(out)
        return out.astype(x.dtype)

    x = jnp.ones((2, 11, 6), jnp.bfloat16)
    w, b = jnp.ones((4, 6)), jnp.ones((6,))
    for activation in (None, "silu"):
        want = _lowered(lambda x, w: before(x, w, activation=activation),
                        x, w)
        assert _lowered(lambda x, w: F._causal_depthwise_conv1d(
            x, w, activation=activation), x, w) == want
        assert _lowered(lambda x, w: F._causal_depthwise_conv1d(
            x, w, None, activation=activation), x, w) == want
    assert _lowered(lambda x, w, b: F._causal_depthwise_conv1d(
        x, w, b, activation="silu"), x, w, b) != want
    # the op a layer's call makes: its name and arguments as before
    from paddle_tpu.core import dispatch

    seen = []
    apply_op = dispatch.apply_op
    F.apply_op = lambda name, fn, *args, **kw: (
        seen.append((name, len(args), sorted(kw))),
        apply_op(name, fn, *args, **kw))[1]
    try:
        F.causal_depthwise_conv1d(paddle.to_tensor(np.ones((1, 5, 6),
                                                           np.float32)),
                                  paddle.to_tensor(np.asarray(w)), "silu")
    finally:
        F.apply_op = apply_op
    assert seen == [("causal_depthwise_conv1d", 2, ["activation"])]
    # the stage: no ``biases`` is ``biases=None`` is the stage before
    xs, ws, segments = conv_form("gdn", 31, jnp.bfloat16, 4, seq=32)

    def stage(**kw):
        return _lowered(lambda xs, ws: la.conv_streams(
            xs, ws, segments, head=CONV_D, eps=1e-6, kernel=None, **kw),
            xs, ws)

    def stage_before(xs, ws):
        def body(xs, ws):
            made = [before(x, w, activation="silu") for x, w in zip(xs, ws)]
            return tuple(
                y if scale is None else la.l2_normed(
                    y, width // CONV_D, eps=1e-6, scale=scale)
                for y, (_, _, width, scale) in (
                    (made[stream][..., start:start + width], seg)
                    for seg in segments
                    for stream, start, width, _ in [seg]))
        return jax.checkpoint(body)(xs, ws)

    assert stage() == stage(biases=None) == _lowered(stage_before, xs, ws)


def test_a_biased_stage_takes_the_kernels_where_the_unbiased_one_does(
        interpreter):
    """``conv_streams(biases=)``: taps, bias, SiLU and the segments' cuts
    against explicit shifted sums, through the kernels: ``conv_path`` and
    ``conv_kernel`` answer what they answer without a bias, and the counter
    says so. What the kernels refuse they refuse with a bias too: taps past
    the carried rows, a normed head that fills no lane group, a short row."""
    xs, ws, _ = conv_form("gdn", 33, jnp.float32, 4, seq=256)
    # Mamba-2's segments: x | B | C of the one stream, none of them normed
    segments = ((0, 0, 6 * CONV_D, None), (0, 6 * CONV_D, CONV_D, None),
                (0, 7 * CONV_D, CONV_D, None))
    bias = jax.random.normal(jax.random.PRNGKey(34), (8 * CONV_D,))
    # heads of 64 (Mamba-2's): no segment is normed, so no head is asked
    for head in (CONV_D, 64):
        assert la.conv_path(256, segments, head, 4, xs[0].dtype) == "kernel"
    assert la.conv_path(256, segments, 64, 10, xs[0].dtype) == "xla"
    assert la.conv_path(255, segments, 64, 4, xs[0].dtype) == "xla"
    normed = ((0, 0, 6 * CONV_D, 1.0),) + segments[1:]
    assert la.conv_path(256, normed, CONV_D, 4, xs[0].dtype) == "kernel"
    assert la.conv_path(256, normed, 64, 4, xs[0].dtype) == "xla"
    before = {p: la._CONV_TOTAL.value(path=p) for p in ("kernel", "xla")}
    assert la.conv_kernel(xs[0], ws[0], segments, 64) == "interpret"
    got = jax.jit(lambda xs, ws, b: la.conv_streams(
        xs, ws, segments, head=64, eps=1e-6, biases=(b,)))(xs, ws, bias)
    assert la._CONV_TOTAL.value(path="kernel") == before["kernel"] + 2
    assert la._CONV_TOTAL.value(path="xla") == before["xla"]
    pre = shifted_multiply_adds(xs[0], np.asarray(ws[0])) + np.asarray(bias)
    want = pre / (1.0 + np.exp(-pre))
    for out, (_, start, width, _) in zip(got, segments):
        np.testing.assert_allclose(out, want[..., start:start + width],
                                   rtol=1e-5, atol=1e-5)
    # its gradient reaches the bias on either path
    sig = 1.0 / (1.0 + np.exp(-pre))
    for kernel in ("interpret", None):
        grad = jax.grad(lambda b: sum(jnp.sum(o) for o in la.conv_streams(
            xs, ws, segments, head=64, eps=1e-6, kernel=kernel,
            biases=(b,))))(bias)
        np.testing.assert_allclose(
            grad, (sig * (1 + pre * (1 - sig))).sum(axis=(0, 1)), rtol=1e-4,
            atol=1e-4)


def one_bf16_ulp(got, want):
    """Every element of ``got`` (bf16) within one bf16 step of the float32
    ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    assert np.isfinite(got).all()
    assert (np.abs(got - want) <= step + 1e-6 * np.abs(want).max()).all()


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("form", ["kda", "gdn"])
def test_the_convolution_kernels_are_the_xla_stage(form, dtype, taps):
    """Forward and the VJP (dx and the taps' gradient) against the XLA
    stage in float32 on the same values: batch 2 (a row's start is zero
    history whatever the row before left), 80 tokens in blocks of 32 (the
    halo crosses two block boundaries forward, the pre-activation's
    gradient two backward, and the last block is padded), the first K - 1
    tokens, two channel steps. Float32 to 1e-5; bf16 results (q, k, v and
    dx) within one bf16 step of the float32 stage's, the taps' gradient —
    float32 sums either way — to 1e-5."""
    xs, ws, segments = conv_form(form, 21, dtype, taps)
    assert kernels.conv_steps(segments, CONV_D, 128) == 2
    keys = jax.random.split(jax.random.PRNGKey(22), len(segments))
    cotangents = tuple(
        jax.random.normal(key, xs[0].shape[:2] + (width,)).astype(dtype)
        for key, (_, _, width, _) in zip(keys, segments))
    f32 = jnp.float32
    want, want_vjp = jax.vjp(
        lambda xs, ws: la._conv_xla(xs, ws, segments, CONV_D, 1e-6),
        tuple(x.astype(f32) for x in xs), ws)
    want_dxs, want_dws = want_vjp(tuple(c.astype(f32) for c in cotangents))
    got, got_vjp = jax.vjp(
        lambda xs, ws: conv_kernels(xs, ws, segments), xs, ws)
    got_dxs, got_dws = got_vjp(cotangents)
    assert [o.dtype for o in got] == [dtype] * 3
    assert [o.shape for o in got] == [w.shape for w in want]
    for a, b in zip(got + got_dxs, want + want_dxs):
        assert a.dtype == dtype
        if dtype == jnp.bfloat16:
            one_bf16_ulp(a, b)
        else:
            close(a, b, 1e-5)
            close(a[:, :taps - 1], b[:, :taps - 1], 1e-5)
    for a, b in zip(got_dws, want_dws):
        assert a.dtype == f32 and a.shape == b.shape
        close(a, b, 1e-5)


def test_a_row_of_whole_blocks_and_one_channel_step():
    """No padding and a program that holds every channel of its segments:
    the blocks a train step's shapes get, scaled down."""
    xs, ws, segments = conv_form("gdn", 23, jnp.float32, 4, seq=64)
    want = la._conv_xla(xs, ws, segments, CONV_D, 1e-6)
    got = conv_kernels(xs, ws, segments, tokens=32, lanes=512)
    for a, b in zip(got, want):
        close(a, b, 1e-5)


#: Mamba-2's stage as granite-4.0-h-micro has it: x | B | C = 4,096 | 128 |
#: 128 channels of ONE biased stream, 64 heads of 64, none of them normed
MAMBA_SEGMENTS = ((0, 0, 4096, None), (0, 4096, 128, None),
                  (0, 4224, 128, None))


def biased_vjps(xs, ws, biases, segments, head, cotangents, **blocks):
    """((outputs, (dxs, dws, dbs)) of the XLA stage in float32, the same of
    the kernels on the values as given)."""
    f32 = jnp.float32
    want, want_vjp = jax.vjp(
        lambda xs, ws, bs: la._conv_xla(xs, ws, segments, head, 1e-6, bs),
        tuple(x.astype(f32) for x in xs), ws, biases)
    got, got_vjp = jax.vjp(
        lambda xs, ws, bs: conv_kernels(xs, ws, segments, biases=bs,
                                        head=head, **blocks), xs, ws, biases)
    return ((want, want_vjp(tuple(c.astype(f32) for c in cotangents))),
            (got, got_vjp(cotangents)))


@pytest.mark.parametrize("dtype, seq, tokens", [
    (jnp.bfloat16, 300, None),      # CONV_TOKENS a block, the last padded
    (jnp.float32, 80, 32),          # three blocks, the last padded
])
def test_the_biased_kernels_are_the_xla_stage_at_granites_segments(
        dtype, seq, tokens):
    """Mamba-2's stage at granite-4.0-h-micro's widths (4,096 | 128 | 128 of
    one stream, heads of 64: ONE channel step, 34 lane groups a program),
    batch 2, a row that is no whole number of blocks: the outputs and the
    gradients of the stream, the taps AND the bias against the XLA stage in
    float32 on the same values. Float32 to 1e-5; bf16 results (x, B, C and
    dx) within one bf16 step, the float32 sums to 1e-5."""
    assert kernels.conv_steps(MAMBA_SEGMENTS, 64) == 1
    assert kernels.conv_supported(MAMBA_SEGMENTS, 64, 4, dtype)
    keys = jax.random.split(jax.random.PRNGKey(41), 6)
    xs = (jax.random.normal(keys[0], (2, seq, 4352)).astype(dtype),)
    ws = (jax.random.normal(keys[1], (4, 4352)) * 0.5,)
    biases = (jax.random.normal(keys[2], (4352,)),)
    cotangents = tuple(
        jax.random.normal(key, (2, seq, width)).astype(dtype)
        for key, (_, _, width, _) in zip(keys[3:], MAMBA_SEGMENTS))
    (want, want_grads), (got, got_grads) = biased_vjps(
        xs, ws, biases, MAMBA_SEGMENTS, 64, cotangents, tokens=tokens,
        lanes=None)
    assert [o.shape for o in got] == [(2, seq, 4096), (2, seq, 128),
                                      (2, seq, 128)]
    for a, b in zip(got + got_grads[0], want + want_grads[0]):
        assert a.dtype == dtype
        if dtype == jnp.bfloat16:
            one_bf16_ulp(a, b)
        else:
            close(a, b, 1e-5)
    for a, b in zip(got_grads[1] + got_grads[2],
                    want_grads[1] + want_grads[2]):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        close(a, b, 1e-5)


@pytest.mark.parametrize("form", ["kda", "gdn"])
def test_a_bias_rides_normed_segments_too(form):
    """The bias is not Mamba-2's alone: a biased stream whose q and k
    segments take the L2 norm (three streams with a bias each; one stream of
    three segments), two channel steps, against the XLA stage — outputs and
    every gradient."""
    xs, ws, segments = conv_form(form, 43, jnp.float32, 4)
    keys = jax.random.split(jax.random.PRNGKey(44), len(xs) + len(segments))
    biases = tuple(jax.random.normal(key, (x.shape[-1],))
                   for key, x in zip(keys, xs))
    cotangents = tuple(
        jax.random.normal(key, xs[0].shape[:2] + (width,))
        for key, (_, _, width, _) in zip(keys[len(xs):], segments))
    (want, want_grads), (got, got_grads) = biased_vjps(
        xs, ws, biases, segments, CONV_D, cotangents)
    for a, b in zip(jax.tree.leaves((got, got_grads)),
                    jax.tree.leaves((want, want_grads))):
        assert a.shape == b.shape
        close(a, b, 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_zero_bias_is_the_unbiased_kernels_bit_for_bit(dtype):
    """``a + 0`` is ``a``: outputs, dx and the taps' gradient of the biased
    kernels at a bias of zero are the unbiased kernels', every bit —
    compiled without XLA's operation fusion, which on a CPU contracts one
    interpreted body's multiply-adds where it leaves the other's."""
    xs, ws, segments = conv_form("gdn", 45, dtype, 4, batch=1, seq=48)
    zeros = (jnp.zeros(xs[0].shape[-1]),)
    cotangents = tuple(
        jax.random.normal(key, xs[0].shape[:2] + (width,)).astype(dtype)
        for key, (_, _, width, _) in zip(
            jax.random.split(jax.random.PRNGKey(46), 3), segments))

    def stage(xs, ws, *biases):
        out, vjp = jax.vjp(lambda xs, ws, *bs: conv_kernels(
            xs, ws, segments, biases=bs[0] if bs else None), xs, ws, *biases)
        return out, vjp(cotangents)[:2]

    def unfused(*args):
        return jax.jit(stage).lower(*args).compile(compiler_options={
            "xla_disable_hlo_passes": "fusion,cpu-instruction-fusion"})(*args)

    for a, b in zip(jax.tree.leaves(unfused(xs, ws, zeros)),
                    jax.tree.leaves(unfused(xs, ws))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _fwd_kernel_before(*refs, segments, head, eps, bias=False):
    """``kernels._conv_fwd_kernel`` as it stood before it took a bias
    (PR 47), written out."""
    f32, halo_rows = jnp.float32, kernels.CONV_HALO
    n = len(segments)
    xs, ws, outs, halos = (refs[i * n:(i + 1) * n] for i in range(4))

    @kernels.pl.when(kernels.pl.program_id(2) == 0)
    def _row_start():
        for halo in halos:
            halo[...] = jnp.zeros(halo.shape, f32)

    for x_ref, w_ref, o_ref, halo, (_, _, _, scale) in zip(
            xs, ws, outs, halos, segments):
        tokens, width = x_ref.shape[1:]
        taps = w_ref.shape[1]
        step = 128 if scale is None else head
        for at in range(0, width, step):
            lanes = slice(at, at + step)
            xf = x_ref[0, :, lanes].astype(f32)
            ext = jnp.concatenate([halo[:, lanes], xf], axis=0)
            halo[:, lanes] = xf[tokens - halo_rows:]
            a = kernels._pre_activation(kernels._shifted(ext, taps),
                                        w_ref[0, :, lanes].astype(f32))
            y = a * jax.nn.sigmoid(a)
            if scale is not None:
                y = y * (jax.lax.rsqrt(kernels._head_sum(y * y) + eps)
                         * scale)
            o_ref[0, :, lanes] = y.astype(o_ref.dtype)


def _bwd_kernel_before(*refs, segments, head, eps, bias=False):
    """``kernels._conv_bwd_kernel`` as it stood before it took a bias
    (PR 47), written out."""
    f32, halo_rows, pl = jnp.float32, kernels.CONV_HALO, kernels.pl
    n = len(segments)
    xs, befores, ws, dys, dxs, dws, carries = (
        refs[i * n:(i + 1) * n] for i in range(7))
    first = pl.program_id(2) == 0
    row_start = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _row_end():
        for dw_ref, carry in zip(dws, carries):
            dw_ref[...] = jnp.zeros(dw_ref.shape, f32)
            carry[...] = jnp.zeros(carry.shape, f32)

    for (x_ref, before_ref, w_ref, dy_ref, dx_ref, dw_ref, carry,
         (_, _, _, scale)) in zip(xs, befores, ws, dys, dxs, dws, carries,
                                  segments):
        tokens, width = x_ref.shape[1:]
        taps = w_ref.shape[1]
        step = 128 if scale is None else head
        for at in range(0, width, step):
            lanes = slice(at, at + step)
            w = w_ref[0, :, lanes].astype(f32)
            before = before_ref[0, :, lanes].astype(f32)[
                kernels._BEFORE - halo_rows:]
            ext = jnp.concatenate(
                [jnp.where(row_start, 0.0, before),
                 x_ref[0, :, lanes].astype(f32)], axis=0)
            shifted = kernels._shifted(ext, taps)
            a = kernels._pre_activation(shifted, w)
            sig = jax.nn.sigmoid(a)
            dy = dy_ref[0, :, lanes].astype(f32)
            if scale is not None:
                y = a * sig
                r = jax.lax.rsqrt(kernels._head_sum(y * y) + eps)
                dy = (dy - y * (r * r * kernels._head_sum(dy * y))) * (
                    r * scale)
            da = dy * (sig * (1.0 + a * (1.0 - sig)))
            dw_ref[0, :, lanes] += jnp.concatenate(
                [jnp.sum(da * shifted[taps - 1 - j], axis=0, keepdims=True)
                 for j in range(taps)], axis=0)
            later = kernels._shifted(
                jnp.concatenate([da, carry[:, lanes]], axis=0), taps,
                up=True)
            carry[:, lanes] = da[:halo_rows]
            dx_ref[0, :, lanes] = kernels._pre_activation(later, w).astype(
                dx_ref.dtype)


@pytest.mark.parametrize("form", ["kda", "gdn"])
def test_the_kernels_without_a_bias_lower_to_what_they_did(form,
                                                           monkeypatch):
    """The pattern of ``test_a_convolution_without_a_bias_lowers_to_what_it_
    did``, through the KERNEL path: the stage's forward and VJP at Kimi Delta
    Attention's three streams and at Gated DeltaNet's one, without a bias,
    lower (in the interpreter, where the bodies are part of the text) to
    the text the bodies of before the bias lower to; with a bias they do
    not."""
    xs, ws, segments = conv_form(form, 47, jnp.bfloat16, 4, batch=1, seq=64)
    zeros = tuple(jnp.zeros(x.shape[-1]) for x in xs)

    def lowered(*biases):
        # the bodies are traced under inner jits: one trace a shape
        kernels._conv_forward.clear_cache()
        kernels._conv_backward.clear_cache()

        def stage(xs, ws, *biases):
            out, vjp = jax.vjp(lambda xs, ws, *bs: conv_kernels(
                xs, ws, segments, tokens=32, biases=bs[0] if bs else None),
                xs, ws, *biases)
            return out, vjp(out)

        return _lowered(stage, xs, ws, *biases)

    now, biased = lowered(), lowered(zeros)
    traced = []

    def body(name, kernel):
        def traced_body(*refs, **static):
            traced.append(name)
            return kernel(*refs, **static)
        return traced_body

    monkeypatch.setattr(kernels, "_conv_fwd_kernel",
                        body("fwd", _fwd_kernel_before))
    monkeypatch.setattr(kernels, "_conv_bwd_kernel",
                        body("bwd", _bwd_kernel_before))
    try:
        before = lowered()
    finally:
        monkeypatch.undo()
        kernels._conv_forward.clear_cache()
        kernels._conv_backward.clear_cache()
    assert sorted(set(traced)) == ["bwd", "fwd"]
    assert "while" in now              # the interpreter's grid, bodies inside
    assert now == before
    assert biased != before


@pytest.mark.parametrize("segments, head, lanes, steps", [
    (((0, 0, 4096, 1.0), (1, 0, 4096, 1.0), (2, 0, 4096, None)), 128, None,
     8),                                             # Kimi-Linear's
    (((0, 0, 2048, 1.0), (0, 2048, 2048, 1.0), (0, 4096, 4096, None)), 128,
     None, 4),                                       # Qwen3-Next's
    (((0, 0, 512, 1.0), (0, 512, 256, None)), 256, 128, 2),   # whole heads
    (((0, 0, 256, 1.0), (0, 256, 192, None)), 128, None, 0),  # half a group
    (((0, 0, 256, 1.0),), 64, None, 0),              # half a lane group a head
    (((0, 128, 256, 1.0),), 128, None, 0),           # off its own blocks
])
def test_the_channel_cut_keeps_heads_whole(segments, head, lanes, steps):
    if steps == 0 and head % 128:
        assert not kernels.conv_supported(segments, head, 4, jnp.bfloat16)
    else:
        assert kernels.conv_steps(segments, head, lanes) == steps
        assert kernels.conv_supported(
            segments, head, 4, jnp.bfloat16) == bool(steps)


KDA_SEGMENTS = ((0, 0, 256, 128 ** -0.5), (1, 0, 256, 1.0),
                (2, 0, 256, None))
GDN_SEGMENTS = ((0, 0, 256, 128 ** -0.5), (0, 256, 256, 1.0),
                (0, 512, 512, None))


@pytest.mark.parametrize("seq, segments, head, taps, dtype, path", [
    (256, KDA_SEGMENTS, 128, 4, jnp.bfloat16, "kernel"),
    (16384, GDN_SEGMENTS, 128, 4, jnp.float32, "kernel"),
    (16384, KDA_SEGMENTS, 128, 9, jnp.bfloat16, "kernel"),
    (255, KDA_SEGMENTS, 128, 4, jnp.bfloat16, "xla"),    # under one block
    (16384, ((0, 0, 64, 0.25), (1, 0, 64, 1.0), (2, 0, 64, None)), 16, 4,
     jnp.bfloat16, "xla"),                               # heads of 16
    (16384, ((0, 0, 192, None),), 128, 4, jnp.bfloat16, "xla"),
    (16384, KDA_SEGMENTS, 128, 10, jnp.bfloat16, "xla"),  # past the halo
    (16384, KDA_SEGMENTS, 128, 4, jnp.float16, "xla"),
])
def test_the_convolution_path_goes_by_widths_dtype_and_length(
        interpreter, seq, segments, head, taps, dtype, path):
    assert la.conv_path(seq, segments, head, taps, dtype) == path


def test_the_convolution_path_needs_a_platform_and_known_devices(
        monkeypatch):
    """No TPU and no interpreter flag: the XLA stage. A platform that
    compiles the kernels but a program whose devices are not known: the XLA
    stage again. Under an announced mesh an 'mp' axis may cut a stream
    between whole heads of one segment, not through q | k | v."""
    from paddle_tpu.distributed import topology

    shape = (16384, KDA_SEGMENTS, 128, 4, jnp.bfloat16)
    assert la.conv_path(*shape) == "xla"
    monkeypatch.setattr(placement, "is_tpu_available", lambda: True)
    assert jax.device_count() > 1                       # conftest's mesh
    assert la.conv_path(*shape) == "xla"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert la.conv_path(*shape) == "kernel"
    for axes, kda, gdn in ((dict(dp=4), "kernel", "kernel"),
                           (dict(dp=2, mp=2), "kernel", "xla"),
                           (dict(mp=4), "xla", "xla")):   # half a head each
        mesh = topology.build_mesh(**axes, devices=jax.devices()[:4])
        with topology.tracing_for(mesh):
            assert la.conv_path(*shape) == kda, axes
            assert la.conv_path(16384, GDN_SEGMENTS, 128, 4,
                                jnp.bfloat16) == gdn, axes


@pytest.mark.parametrize("seq, head, path", [(256, 128, "kernel"),
                                             (300, 128, "kernel"),
                                             (200, 128, "xla"),
                                             (256, 16, "xla")])
def test_the_stage_counts_the_path_once_a_trace(interpreter, seq, head,
                                                path):
    """One count a traced call, under the path taken, and either path's
    result is the XLA stage's."""
    wide = 2 * head
    keys = jax.random.split(jax.random.PRNGKey(24), 2)
    xs = (jax.random.normal(keys[0], (1, seq, 4 * wide)),)
    ws = (jax.random.normal(keys[1], (4, 4 * wide)) * 0.5,)
    segments = ((0, 0, wide, head ** -0.5), (0, wide, wide, 1.0),
                (0, 2 * wide, 2 * wide, None))
    before = {p: la._CONV_TOTAL.value(path=p) for p in ("kernel", "xla")}
    stage = jax.jit(lambda xs, ws: la.conv_streams(
        xs, ws, segments, head=head, eps=1e-6))
    got = stage(xs, ws)
    stage(xs, ws)                                   # traced once
    for p, n in before.items():
        assert la._CONV_TOTAL.value(path=p) == n + (p == path), p
    for a, b in zip(got, la._conv_xla(xs, ws, segments, head, 1e-6)):
        close(a, b, 1e-5)


@pytest.mark.parametrize("biased", [False, True])
def test_the_convolution_kernels_shard_over_an_announced_mesh(interpreter,
                                                              biased):
    """Inside a step traced for a mesh the stage runs under the attention
    kernels' ``shard_map``: rows over the data axis and — three streams of
    one segment each — heads over 'mp'; the taps go a copy a row (a bias
    its last row, cut with them), so their gradient is summed over the
    shards. Result and gradients are the one-device ones."""
    from paddle_tpu.distributed import topology

    xs, ws, segments = conv_form("kda", 25, jnp.float32, 4, seq=256)
    biases = tuple(jax.random.normal(key, (x.shape[-1],)) for key, x in zip(
        jax.random.split(jax.random.PRNGKey(27), 3), xs)) if biased else None
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    weights = jax.random.normal(jax.random.PRNGKey(26), (3,) + xs[0].shape)

    def loss(stage):
        return lambda *args: sum(jnp.sum(o * c) for o, c in
                                 zip(stage(*args), weights))

    def on_mesh(xs, ws, biases=None):
        with topology.tracing_for(mesh):
            assert la.conv_path(256, segments, CONV_D, 4, xs[0].dtype) == (
                "kernel")
            return la.conv_streams(xs, ws, segments, head=CONV_D, eps=1e-6,
                                   biases=biases)

    args = (xs, ws) + ((biases,) if biased else ())
    text = jax.jit(on_mesh).lower(*args).as_text()
    assert "shard_map" in text or "manual" in text
    specs = [str(s) for s in _shard_map_in_specs(on_mesh, args)]
    # three streams and their taps, every one cut both ways; the seed not
    assert sum("mp" in s and "dp" in s for s in specs) == 6, specs
    argnums = tuple(range(len(args)))
    want = jax.value_and_grad(loss(
        lambda xs, ws, biases=None: la._conv_xla(
            xs, ws, segments, CONV_D, 1e-6, biases)), argnums)(*args)
    got = jax.jit(jax.value_and_grad(loss(on_mesh), argnums))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 1e-5)


@pytest.mark.parametrize("kind", ["kda", "gdn", "mamba"])
def test_a_layer_takes_the_convolution_kernels_through_the_tape(kind):
    """The layers' own call at lane-wide heads, eagerly: Tensors in,
    ``backward`` through the tape, a row that is no whole block; with the
    interpreter flag the stage counts ``kernel`` (a count a trace: the
    forward's and the tape's), without it ``xla``, and the flag's flip
    retraces (the stage's static arguments carry it). The two agree on the
    output and on every gradient."""
    from paddle_tpu.text.models import (GatedDeltaNet, KimiDeltaAttention,
                                        Mamba2Mixer)

    paddle.seed(3)
    layer = {"kda": lambda: KimiDeltaAttention(32, num_heads=2, head_dim=128),
             "gdn": lambda: GatedDeltaNet(32, num_k_heads=1, num_v_heads=2),
             # heads of 64 and a bias: x | B | C of 128 channels each
             "mamba": lambda: Mamba2Mixer(32, num_heads=2, head_dim=64,
                                          d_state=128)}[kind]()
    x = np.random.default_rng(4).standard_normal((2, 260, 32)).astype(
        np.float32)
    results = {}
    for path, flag in (("xla", False), ("kernel", True), ("xla", False)):
        other = "xla" if path == "kernel" else "kernel"
        before = {p: la._CONV_TOTAL.value(path=p) for p in (path, other)}
        paddle.set_flags({"pallas_interpret": flag})
        try:
            layer.clear_gradients()
            given = paddle.to_tensor(x, stop_gradient=False)
            out = layer(given)
            (out * out).sum().backward()
        finally:
            paddle.set_flags({"pallas_interpret": False})
        assert la._CONV_TOTAL.value(path=other) == before[other]
        if path not in results:
            assert la._CONV_TOTAL.value(path=path) > before[path]
        results.setdefault(path, [out._value, given.grad._value] + [
            p.grad._value for p in layer.parameters()])
    assert len(results["kernel"]) > 6
    for a, b in zip(results["kernel"], results["xla"]):
        close(a, b, 2e-5)
