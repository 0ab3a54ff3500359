"""F.gelu's exact form, ``0.5 * x * (1 + erf(x / sqrt(2)))`` in float32
(reference: operators/gelu_op.h), against the same expression in float64.

What the form keeps and what it gives up is pinned here: float32 stays
within 4e-7 * max(1, |x|) of the float64 value in ABSOLUTE terms; below
x ~ -5, where |gelu| < 1e-6, ``1 + erf`` cancels (XLA's float32 erf
saturates a few ulp short of -1) and the relative accuracy is gone. A half
input is computed in float32 and rounded once: within one ulp of the
float64 value rounded to that dtype, where ``jax.nn.gelu``'s erfc form on
half inputs (a bf16 ``sqrt(0.5)`` and three roundings) is several ulp off.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import amp, nn

SQRT_HALF = math.sqrt(0.5)
HALVES = {"bfloat16": jnp.bfloat16, "float16": jnp.float16}


def gelu64(x):
    return np.array([0.5 * v * (1.0 + math.erf(v * SQRT_HALF))
                     for v in np.asarray(x, np.float64).ravel()])


def dgelu64(x):
    """Phi(x) + x * phi(x)."""
    x = np.asarray(x, np.float64).ravel()
    return np.array([0.5 * (1.0 + math.erf(v * SQRT_HALF))
                     + v * math.exp(-0.5 * v * v) / math.sqrt(2 * math.pi)
                     for v in x])


def ulps(got, want64, dtype):
    """|got - want| in units of ``dtype``'s spacing at ``want``, ``want``
    being the float64 value rounded once to ``dtype``."""
    info = jnp.finfo(dtype)
    want = np.asarray(want64).astype(dtype).astype(np.float64)
    got = np.asarray(got).astype(np.float64)
    scale = np.maximum(np.abs(want), float(info.smallest_normal))
    return np.abs(got - want) / (2.0 ** np.floor(np.log2(scale))
                                 * float(info.eps))


def grid(lo, hi, n, dtype):
    """``n`` points of [lo, hi] as ``dtype`` holds them, and in float64."""
    x = jnp.asarray(np.linspace(lo, hi, n), dtype)
    return x, np.asarray(x.astype(jnp.float32)).astype(np.float64)


@pytest.mark.parametrize("lo,hi", [(-12, -5), (-5, -1), (-1, 1), (1, 5),
                                   (5, 12)])
def test_float32_absolute_error(lo, hi):
    x, x64 = grid(lo, hi, 20001, jnp.float32)
    got = F.gelu(paddle.Tensor(x)).numpy()
    assert got.dtype == np.float32
    err = np.abs(got.astype(np.float64) - gelu64(x64))
    assert np.max(err / np.maximum(1.0, np.abs(x64))) <= 4e-7


@pytest.mark.parametrize("value", ["+0", "-0", "+inf", "-inf", "nan"])
def test_float32_special_values(value):
    x = np.float32(value.lstrip("+"))
    got = F.gelu(paddle.to_tensor(np.array([x]))).numpy()[0]
    if value == "nan":
        assert np.isnan(got)
    elif value == "+inf":
        assert got == np.inf
    elif value == "-inf":
        # the limit is -0. In float64 the expression is inf * 0 = nan; in
        # float32 it is that, or -inf where erf stops short of -1
        assert np.isnan(got) or got <= 0
    else:
        assert got == 0 and np.signbit(got) == np.signbit(x)


@pytest.mark.parametrize("dtype,lo", [("bfloat16", -4.0), ("float16", -3.75)])
def test_half_within_one_ulp(dtype, lo):
    """One rounding at the end. float16 is finer than float32's 1 + erf
    below -3.8 (its tail is the next case)."""
    x, x64 = grid(lo, 4, 8001, HALVES[dtype])
    got = F.gelu(paddle.Tensor(x))._value
    assert got.dtype == HALVES[dtype]
    assert np.max(ulps(got, gelu64(x64), HALVES[dtype])) <= 1.0


def test_float16_tail_keeps_the_float32_bound():
    x, x64 = grid(-8, -3.75, 4001, jnp.float16)
    got = np.asarray(F.gelu(paddle.Tensor(x))._value).astype(np.float64)
    want = gelu64(x64)
    # float32's bound, then one rounding to float16 (half a spacing)
    rounding = np.maximum(np.abs(want) * 2.0 ** -11, 2.0 ** -25)
    assert np.all(np.abs(got - want) <= 4e-7 * np.abs(x64) + rounding)


def erfc_form_in(dtype, x):
    """``jax.nn.gelu(approximate=False)`` on a half input, op for op as
    jax writes it — ``x * erfc(-x * sqrt_half) / 2`` with ``sqrt_half``
    cast to the dtype and every op's result rounded to it (how far a
    compiler fuses those roundings away is its own business)."""
    def rounded(v):
        return np.asarray(v, np.float64).astype(dtype).astype(np.float64)

    x = rounded(x)
    arg = rounded(-x * rounded(SQRT_HALF))
    erfc = rounded([math.erfc(v) for v in arg])
    return rounded(rounded(x * erfc) / 2).astype(dtype)


@pytest.mark.parametrize("dtype", list(HALVES))
def test_more_exact_than_the_erfc_form_on_half_inputs(dtype):
    """The claim the change makes for amp O1 programs, as a test: the erfc
    form in a half dtype is several ulp off at its worst (a bf16 0.707 is
    1.1e-4 short, and three results are rounded), this form never more
    than one."""
    x, x64 = grid(-3.75, 4, 8001, HALVES[dtype])
    want = gelu64(x64)
    new = np.max(ulps(F.gelu(paddle.Tensor(x))._value, want, HALVES[dtype]))
    old = np.max(ulps(erfc_form_in(HALVES[dtype], x64), want, HALVES[dtype]))
    assert new <= 1.0 and old >= 3.0


def test_float64_stays_float64():
    with jax.enable_x64(True):
        x = jnp.asarray(np.linspace(-8, 8, 1601), jnp.float64)
        got = F._gelu(x, approx=False)
        assert got.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(got), gelu64(np.asarray(x)),
                                   rtol=0, atol=1e-15)


def test_float32_gradient():
    x, x64 = grid(-12, 12, 24001, jnp.float32)
    got = jax.vmap(jax.grad(
        lambda v: F.gelu(paddle.Tensor(v))._value))(x)
    assert got.dtype == jnp.float32
    assert np.max(np.abs(np.asarray(got).astype(np.float64)
                         - dgelu64(x64))) <= 1e-6


@pytest.mark.parametrize("dtype", list(HALVES))
def test_half_gradient_dtype_and_value(dtype):
    x, x64 = grid(-4, 4, 801, HALVES[dtype])
    got = jax.vmap(jax.grad(
        lambda v: F.gelu(paddle.Tensor(v))._value))(x)
    assert got.dtype == HALVES[dtype]
    # 1.13 at its largest: two ulp there
    np.testing.assert_allclose(np.asarray(got).astype(np.float64),
                               dgelu64(x64), rtol=0,
                               atol=2 * float(jnp.finfo(HALVES[dtype]).eps))


def test_eager_backward_through_the_tape():
    x = paddle.to_tensor(np.linspace(-3, 3, 13).astype(np.float32),
                         stop_gradient=False)
    F.gelu(x).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), dgelu64(x.numpy()),
                               rtol=0, atol=1e-6)


def test_amp_o1_block_keeps_bf16_between_the_gemms():
    """"gelu" is in neither amp list: it takes linear1's bf16 output and
    hands bf16 to linear2; only inside is it float32."""
    paddle.seed(0)
    up, down = nn.Linear(16, 64), nn.Linear(64, 16)
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 16)
                         .astype(np.float32))
    assert "gelu" not in amp.white_list | amp.black_list
    with amp.auto_cast(level="O1"):
        h = up(x)
        g = F.gelu(h)
        out = down(g)
    assert str(h.dtype).endswith("bfloat16")
    assert str(g.dtype).endswith("bfloat16")
    assert str(out.dtype).endswith("bfloat16")
    want = gelu64(np.asarray(h._value.astype(jnp.float32)))
    assert np.max(ulps(g._value.ravel(), want, jnp.bfloat16)) <= 1.0

    def block(v):
        with amp.auto_cast(level="O1"):
            return F.gelu(paddle.Tensor(v))._value

    erf, = [line for line in str(jax.make_jaxpr(block)(h._value))
            .splitlines() if " erf " in line]
    assert ":f32[" in erf.split("=")[0]


def test_forward_mode_and_second_order():
    """The derivative is a ``custom_jvp``: forward mode and a gradient of
    a gradient go through it (``incubate.autograd.jvp`` / ``hessian``)."""
    x = jnp.asarray(np.linspace(-3, 3, 13), jnp.float32)
    fn = lambda v: F._gelu(v, approx=False)  # noqa: E731
    _, tangent = jax.jvp(fn, (x,), (jnp.ones_like(x),))
    np.testing.assert_allclose(np.asarray(tangent), dgelu64(np.asarray(x)),
                               rtol=0, atol=1e-6)
    second = jax.vmap(jax.grad(jax.grad(fn)))(x)
    x64 = np.asarray(x, np.float64)
    want = (2 - x64 * x64) * np.exp(-0.5 * x64 * x64) / math.sqrt(2 * math.pi)
    np.testing.assert_allclose(np.asarray(second), want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_approximate_is_jax_nn_gelu_tanh_untouched(dtype):
    """The same program (jaxpr for jaxpr), so the same bits."""
    x = jnp.asarray(np.linspace(-8, 8, 4001), dtype)
    ours = jax.make_jaxpr(lambda v: F._gelu(v, approx=True))(x)
    jaxs = jax.make_jaxpr(lambda v: jax.nn.gelu(v, approximate=True))(x)
    assert str(ours) == str(jaxs) and "tanh" in str(ours)
    got = F.gelu(paddle.Tensor(x), approximate=True)._value
    want = jax.jit(lambda v: jax.nn.gelu(v, approximate=True))(x)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("entry", ["nn.GELU", "fluid.layers.gelu"])
def test_every_entry_is_this_function(entry):
    x = paddle.to_tensor(np.linspace(-4, 4, 33).astype(np.float32))
    layer = nn.GELU() if entry == "nn.GELU" else paddle.fluid.layers.gelu
    assert np.array_equal(layer(x).numpy(), F.gelu(x).numpy())
