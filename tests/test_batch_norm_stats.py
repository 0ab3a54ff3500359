"""Batch norm's training statistics from ONE pass over the input
(``F.batch_norm``, PR 28): ``mean = c + E[x-c]``, ``var = E[(x-c)^2] -
E[x-c]^2`` about the running mean ``c``. Held here: the statistics and the
output against a float64 two-pass reference within the bound the shifted
form has, ``eps_f32 * (1 + (mean-c)^2/var)`` times what a float32 sum of n
terms may lose (n/2 at worst when it is taken in sequence, as XLA's CPU
backend does; bf16-valued inputs come near it, because ``x - c`` then
rounds the same way term after term), for every layout, for float32 and
bf16-under-amp inputs, and for a running mean 0, 3 and 30 standard
deviations from the batch mean; the
gradients against autodiff of the two-pass form; the edges (a constant
channel, a poisoned running mean); what did not change (the running
update, eval mode and ``use_global_stats`` bit for bit); and that a
dp-sharded batch gives the GLOBAL batch's statistics.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

EPS32 = float(np.finfo(np.float32).eps)
BN_EPS = 1e-5
C = 5
#: layout -> (shape, channel axis)
LAYOUTS = {"NCHW": ((6, C, 7, 5), 1), "NHWC": ((6, 7, 5, C), 3),
           "NCL": ((8, C, 11), 1), "NCDHW": ((3, C, 4, 5, 3), 1)}
#: the few float32 roundings around a sum: what "about eps" allows
SLACK = 16.0


def _sum_slack(x):
    """What a float32 sum over a channel's n elements may lose, in eps."""
    return max(SLACK, x.size / C / 2)


def t(a, stop_gradient=True):
    return paddle.to_tensor(a, stop_gradient=stop_gradient)


def _batch(layout, seed=0):
    """x with channel means 0.5-4.5 and standard deviations 0.3-1.5."""
    shape, ax = LAYOUTS[layout]
    rng = np.random.RandomState(seed)
    bshape = [1] * len(shape)
    bshape[ax] = C
    mu = np.linspace(0.5, 4.5, C).reshape(bshape)
    sd = np.linspace(0.3, 1.5, C).reshape(bshape)
    return (rng.standard_normal(shape) * sd + mu).astype(np.float32), ax


def _two_pass64(x, ax, w=None, b=None):
    """The float64 reference: mean, then the mean of squared deviations."""
    x = np.asarray(x, np.float64)
    axes = tuple(i for i in range(x.ndim) if i != ax)
    mean = x.mean(axes, keepdims=True)
    var = ((x - mean) ** 2).mean(axes, keepdims=True)
    y = (x - mean) / np.sqrt(var + BN_EPS)
    bshape = [1] * x.ndim
    bshape[ax] = -1
    if w is not None:
        y = y * np.asarray(w, np.float64).reshape(bshape)
    if b is not None:
        y = y + np.asarray(b, np.float64).reshape(bshape)
    return y, mean.reshape(-1), var.reshape(-1)


def _train(x, layout, running_mean, w=None, b=None, amp=False):
    """(y, batch mean, batch var) as the op computed them: momentum 0 makes
    the running buffers after the call the batch statistics themselves."""
    rm = t(np.asarray(running_mean, np.float32))
    rv = t(np.ones(C, np.float32))
    args = (t(x), rm, rv, None if w is None else t(w),
            None if b is None else t(b))
    kw = dict(training=True, momentum=0.0, epsilon=BN_EPS,
              data_format=layout)
    if amp:
        with paddle.amp.auto_cast(level="O1"):
            y = F.batch_norm(*args, **kw)
    else:
        y = F.batch_norm(*args, **kw)
    return y, rm.numpy(), rv.numpy()


@pytest.mark.parametrize("sds", [0, 3, 30])
@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16-amp"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_statistics_match_two_pass_float64(layout, amp, sds):
    x, ax = _batch(layout)
    if amp:   # bf16 activations, as a white-listed convolution hands over
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16))
    x64 = np.asarray(jnp.asarray(x).astype(jnp.float32), np.float64)
    w = np.linspace(0.5, 1.5, C).astype(np.float32)
    b = np.linspace(-1, 1, C).astype(np.float32)
    y_ref, mean_ref, var_ref = _two_pass64(x64, ax, w, b)
    c = mean_ref - sds * np.sqrt(var_ref)
    y, mean, var = _train(x, layout, c, w, b, amp=amp)
    assert y.numpy().dtype == np.float32 and mean.dtype == np.float32

    slack = _sum_slack(x)
    rel = EPS32 * (1.0 + float(sds) ** 2) * slack       # the stated bound
    std = np.sqrt(var_ref)
    np.testing.assert_array_less(np.abs(var - var_ref) / var_ref, rel)
    # the mean: c + E[x-c], a float32 sum of terms of size |x - c|
    np.testing.assert_array_less(
        np.abs(mean - mean_ref),
        EPS32 * slack * (np.abs(mean_ref) + (1 + sds) * std))
    # y = (x - mean) * rsqrt(var + eps): half var's relative error on |y|,
    # the mean's error in units of std, a float32 rounding of the result
    bshape = [1] * x64.ndim
    bshape[ax] = -1
    tol = (np.abs(y_ref) + 1.0) * (rel + EPS32 * slack * (
        (np.abs(mean_ref) / std).reshape(bshape) + 2 + sds))
    assert np.all(np.abs(y.numpy() - y_ref) <= tol)


def _two_pass_loss(x, w, b, ax, cot):
    axes = tuple(i for i in range(x.ndim) if i != ax)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    bshape = [1] * x.ndim
    bshape[ax] = -1
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    return jnp.sum((y * w.reshape(bshape) + b.reshape(bshape)) * cot)


@pytest.mark.parametrize("sds", [0, 3])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_gradients_match_autodiff_of_two_pass(layout, sds):
    x, ax = _batch(layout, seed=1)
    _, mean_ref, var_ref = _two_pass64(x, ax)
    w = np.linspace(0.5, 1.5, C).astype(np.float32)
    b = np.linspace(-1, 1, C).astype(np.float32)
    cot = np.random.RandomState(2).standard_normal(x.shape).astype(np.float32)
    want = jax.grad(_two_pass_loss, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), ax, jnp.asarray(cot))

    xt, wt, bt = (t(a, stop_gradient=False) for a in (x, w, b))
    rm = t((mean_ref - sds * np.sqrt(var_ref)).astype(np.float32))
    y = F.batch_norm(xt, rm, t(np.ones(C, np.float32)), wt, bt,
                     training=True, epsilon=BN_EPS, data_format=layout)
    (y * t(cot)).sum().backward()
    for got, ref in zip((xt.grad, wt.grad, bt.grad), want):
        ref = np.asarray(ref)
        np.testing.assert_allclose(got.numpy(), ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())
    assert rm.grad is None


@pytest.mark.parametrize("value,n,exact", [(1.5, 64, True), (0.1, 1000, False),
                                           (-3e4, 777, False)])
def test_constant_channel_has_zero_variance(value, n, exact):
    """Every element equal: the variance is 0 and never negative, so
    rsqrt(var + eps) is finite, and so are y and every gradient. Where the
    float32 sums are exact (a power of two of exactly representable
    values) it is 0 exactly; elsewhere it is a rounding of 0, far under
    eps_f32 * value^2 * slack."""
    x = np.full((n, 2), value, np.float32)
    x[:, 1] = np.random.RandomState(0).standard_normal(n)
    xt = t(x, stop_gradient=False)
    wt = t(np.ones(2, np.float32), stop_gradient=False)
    rm, rv = t(np.zeros(2, np.float32)), t(np.ones(2, np.float32))
    y = F.batch_norm(xt, rm, rv, wt, None, training=True, momentum=0.0,
                     epsilon=BN_EPS)
    (y * y).sum().backward()
    var = rv.numpy()[0]
    assert var >= 0.0 and var <= EPS32 * SLACK * value * value
    if exact:
        assert var == 0.0 and np.all(y.numpy()[:, 0] == 0.0)
    assert np.isfinite(y.numpy()).all()
    assert np.isfinite(xt.grad.numpy()).all()
    assert np.isfinite(wt.grad.numpy()).all()


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_non_finite_running_mean_stays_out_of_the_batch_statistics(poison):
    x, ax = _batch("NCHW", seed=3)
    y_ref, _, _ = _two_pass64(x, ax)
    c = np.zeros(C, np.float32)
    c[1] = poison
    y, _, var = _train(x, "NCHW", c)
    assert np.isfinite(y.numpy()).all() and np.isfinite(var).all()
    np.testing.assert_allclose(y.numpy(), y_ref, atol=1e-4)


@pytest.mark.parametrize("mode", ["eager", "train_step"])
def test_running_statistics_after_a_step_are_the_old_update(mode):
    """running <- momentum * running + (1 - momentum) * batch statistic,
    the variance the biased one, in eager mode (in place) and threaded out
    of a compiled train step."""
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import spmd, topology

    x, ax = _batch("NCHW", seed=4)
    _, mean_ref, var_ref = _two_pass64(x, ax)
    bn = nn.BatchNorm2D(C, momentum=0.8)
    bn.train()
    bn._mean.set_value(jnp.full((C,), 0.25, jnp.float32))
    bn._variance.set_value(jnp.full((C,), 2.0, jnp.float32))
    if mode == "eager":
        bn(t(x))
    else:
        opt = optimizer.SGD(0.0, parameters=bn.parameters())
        step, init = spmd.build_train_step(
            bn, lambda o, y: jnp.mean(o * o), opt,
            mesh=topology.build_mesh(dp=1, devices=jax.devices()[:1]))
        params, st = init()
        step(params, st, x, np.zeros((x.shape[0],), np.float32))
    np.testing.assert_allclose(bn._mean.numpy(),
                               0.8 * 0.25 + 0.2 * mean_ref, rtol=1e-5)
    np.testing.assert_allclose(bn._variance.numpy(),
                               0.8 * 2.0 + 0.2 * var_ref, rtol=1e-5)


@pytest.mark.parametrize("mode", ["eval", "use_global_stats"])
def test_running_statistics_path_is_bit_for_bit_the_old_one(mode):
    """Eval mode and ``use_global_stats=True`` never see the batch: the
    same expression, in the same order, as before — equal to the bit."""
    x, ax = _batch("NCHW", seed=5)
    rng = np.random.RandomState(6)
    rm = rng.standard_normal(C).astype(np.float32)
    rv = (rng.rand(C) + 0.5).astype(np.float32)
    w = rng.standard_normal(C).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32)
    rmt, rvt = t(rm), t(rv)
    kw = (dict(training=False) if mode == "eval"
          else dict(training=True, use_global_stats=True))
    y = F.batch_norm(t(x), rmt, rvt, t(w), t(b), epsilon=BN_EPS, **kw)

    @jax.jit    # eager ops run jitted: the same expression, op for op
    def old(x, rm, rv, w, b):
        shape = (1, C, 1, 1)
        inv = jax.lax.rsqrt(rv.reshape(shape) + BN_EPS)
        return (x - rm.reshape(shape)) * inv * w.reshape(shape) \
            + b.reshape(shape)

    want = old(x, rm, rv, w, b)
    assert np.array_equal(y.numpy(), np.asarray(want))
    assert np.array_equal(rmt.numpy(), rm) and np.array_equal(rvt.numpy(), rv)


def test_sync_batch_norm_on_a_dp_mesh_uses_the_global_batch():
    """A batch sharded over dp=4: each device holds rows with another
    mean, and the statistics that come back are the whole batch's (XLA
    reduces both sums across the replicas), not a shard's."""
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import spmd, topology

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    rng = np.random.RandomState(7)
    x = rng.standard_normal((16, C, 3, 3)).astype(np.float32)
    x += np.repeat(np.arange(4, dtype=np.float32) * 5.0, 4).reshape(16, 1, 1, 1)
    _, mean_ref, var_ref = _two_pass64(x, 1)
    shard_mean = x[:4].mean((0, 2, 3))
    assert np.all(np.abs(shard_mean - mean_ref) > 5.0)

    model = nn.SyncBatchNorm.convert_sync_batchnorm(
        nn.Sequential(nn.BatchNorm2D(C, momentum=0.0)))
    assert isinstance(model[0], nn.SyncBatchNorm)
    model.train()
    opt = optimizer.SGD(0.0, parameters=model.parameters())
    mesh = topology.build_mesh(dp=4, devices=jax.devices()[:4])
    step, init = spmd.build_train_step(
        model, lambda o, y: jnp.mean(o * o), opt, mesh=mesh)
    params, st = init()
    loss, params, st = step(params, st, x, np.zeros((16,), np.float32))
    np.testing.assert_allclose(model[0]._mean.numpy(), mean_ref, rtol=1e-5)
    np.testing.assert_allclose(model[0]._variance.numpy(), var_ref, rtol=1e-5)
    # y is normalised by the global statistics: mean(y^2) = var/(var+eps)
    np.testing.assert_allclose(float(loss), np.mean(var_ref / (var_ref + 1e-5)),
                               rtol=1e-5)
