"""LFM2's gated short convolution as Mosaic kernels
(``ops/pallas/linear_attention.py``: ``gated_conv_fwd`` / ``_bwd``, here in
the Pallas interpreter) against the XLA stage every other program runs
(``ops.linear_attention._gated_xla``): the result, the cotangent of all
three thirds of ``bcu`` and the taps' gradient, across token blocks and lane
groups, rows that are no whole block, rows that must not see one another,
causality, an announced mesh, and the layer's own two-argument call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch
from paddle_tpu.distributed import topology
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops.pallas import linear_attention as kernels
# the sibling kernels' helpers (the convolution stage's tests): tolerances,
# the interpreter flag's fixture, the first shard_map's in_specs
from test_linear_attention import (_shard_map_in_specs, close,  # noqa: F401
                                   interpreter, one_bf16_ulp)


def stage_inputs(seed, batch, seq, channels, taps, dtype):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcu = jax.random.normal(keys[0], (batch, seq, 3 * channels)).astype(dtype)
    w = jax.random.normal(keys[1], (taps, channels)) * 0.5
    dy = jax.random.normal(keys[2], (batch, seq, channels)).astype(dtype)
    return bcu, w, dy


def gated_kernels(bcu, w, tokens=32):
    """The kernels as the op calls them (the taps a copy a batch row), at a
    block small enough that a row is several of them."""
    rows = jnp.broadcast_to(w[None], (bcu.shape[0],) + w.shape)
    return kernels.gated_conv(bcu, rows, tokens=tokens, interpret=True)


@pytest.mark.parametrize("channels", [128, 384])
@pytest.mark.parametrize("taps", [3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_gated_kernels_are_the_xla_stage(dtype, taps, channels):
    """Forward and the VJP against the XLA stage in float32 on the same
    values: batch 2 (a row's start is zero history whatever the row before
    left), 80 tokens in blocks of 32 (the carried rows cross two block
    boundaries forward, the cotangent's two backward in the reversed walk,
    and the last block is padded), one lane group and three. Float32 to
    1e-5; bf16 results (the stage's and all three thirds of d bcu) within
    one bf16 step of the float32 stage's, the taps' gradient — float32 sums
    either way — to 1e-5."""
    bcu, w, dy = stage_inputs(31, 2, 80, channels, taps, dtype)
    f32 = jnp.float32
    want, want_vjp = jax.vjp(la._gated_xla, bcu.astype(f32), w)
    want_dbcu, want_dw = want_vjp(dy.astype(f32))
    got, got_vjp = jax.vjp(gated_kernels, bcu, w)
    got_dbcu, got_dw = got_vjp(dy)
    assert got.dtype == dtype and got.shape == want.shape
    assert got_dbcu.dtype == dtype and got_dbcu.shape == bcu.shape
    thirds = [slice(i * channels, (i + 1) * channels) for i in range(3)]
    for a, b in [(got, want)] + [(got_dbcu[..., s], want_dbcu[..., s])
                                 for s in thirds]:
        if dtype == jnp.bfloat16:
            one_bf16_ulp(a, b)
        else:
            close(a, b, 1e-5)
            close(a[:, :taps - 1], b[:, :taps - 1], 1e-5)
    assert got_dw.dtype == w.dtype and got_dw.shape == w.shape
    close(got_dw, want_dw, 1e-5)


def test_a_row_of_whole_blocks_is_not_padded():
    bcu, w, dy = stage_inputs(32, 1, 64, 128, 3, jnp.float32)
    want, want_vjp = jax.vjp(la._gated_xla, bcu, w)
    got, got_vjp = jax.vjp(gated_kernels, bcu, w)
    close(got, want, 1e-5)
    for a, b in zip(got_vjp(dy), want_vjp(dy)):
        close(a, b, 1e-5)


def test_a_rows_first_tokens_see_zeros_and_never_the_row_before():
    """Three rows at once are each the row alone (forward and backward),
    and joining two rows into one — what ``lfm2_check.py --rows-joined``
    does to the program — moves the second row's first K - 1 tokens and
    nothing else."""
    bcu, w, dy = stage_inputs(33, 3, 48, 128, 3, jnp.float32)
    together, vjp = jax.vjp(gated_kernels, bcu, w)
    d_together, _ = vjp(dy)
    for r in range(3):
        alone, vjp = jax.vjp(gated_kernels, bcu[r:r + 1], w)
        np.testing.assert_allclose(together[r], alone[0], rtol=1e-6,
                                   atol=1e-7)
        np.testing.assert_allclose(d_together[r], vjp(dy[r:r + 1])[0][0],
                                   rtol=1e-6, atol=1e-7)
    joined = gated_kernels(bcu[:2].reshape(1, 96, -1), w).reshape(2, 48, -1)
    np.testing.assert_array_equal(joined[0], together[0])
    np.testing.assert_array_equal(joined[1, 2:], together[1, 2:])
    assert float(jnp.abs(joined[1, :2] - together[1, :2]).max()) > 1e-2


@pytest.mark.parametrize("moved", [0, 30, 31, 45, 69])
def test_the_gated_kernels_are_causal_and_reach_their_taps(moved):
    """A change at token t of row 0 moves the outputs at t .. t + K - 1 of
    that row — across a block boundary too (blocks of 32: 30 and 31 reach
    into the next) — and nothing before t, nothing later, nothing in row
    1."""
    taps = 3
    bcu, w, _ = stage_inputs(34, 2, 70, 128, taps, jnp.float32)
    a = np.asarray(gated_kernels(bcu, w))
    b = np.asarray(gated_kernels(bcu.at[0, moved].add(1.0), w))
    changed = np.abs(a - b).max(axis=-1) > 0
    assert np.flatnonzero(changed[0]).tolist() == list(
        range(moved, min(moved + taps, 70)))
    assert not changed[1].any()


def test_the_gated_kernels_shard_over_an_announced_mesh(interpreter):
    """Inside a step traced for a dp2 x mp2 mesh the stage runs under the
    attention kernels' ``shard_map``: rows over the data axis, whole on
    both devices of 'mp' (3 C is not cut); the taps go a copy a row, so
    their gradient is summed over the shards. Result and gradients are the
    XLA stage's."""
    channels = 128
    seq = kernels.gated_conv_tokens(channels, jnp.float32)
    bcu, w, dy = stage_inputs(35, 2, seq, channels, 3, jnp.float32)
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    def on_mesh(bcu, w):
        with topology.tracing_for(mesh):
            return la.gated_short_conv(bcu, w)

    before = la._SHORTCONV_TOTAL.value(path="kernel")
    text = jax.jit(on_mesh).lower(bcu, w).as_text()
    assert la._SHORTCONV_TOTAL.value(path="kernel") == before + 1
    assert "shard_map" in text or "manual" in text
    specs = [str(s) for s in _shard_map_in_specs(on_mesh, (bcu, w))]
    # the stream and the rows of taps over 'dp', nothing over 'mp'
    assert sum("dp" in s for s in specs) == 2, specs
    assert not any("mp" in s for s in specs), specs

    def loss(stage):
        return lambda bcu, w: jnp.sum(stage(bcu, w) * dy)

    want = jax.value_and_grad(loss(la._gated_xla), argnums=(0, 1))(bcu, w)
    got = jax.jit(jax.value_and_grad(loss(on_mesh), argnums=(0, 1)))(bcu, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 1e-5)


@pytest.mark.parametrize("hidden, path", [(128, "kernel"), (64, "xla")])
def test_the_layer_takes_the_kernels_through_its_two_argument_call(
        interpreter, hidden, path):
    """``Lfm2ShortConv`` eagerly, Tensors in and ``backward`` through the
    tape, a row that is no whole block: at 128 channels the stage counts
    ``kernel`` (a count a trace: the forward's and the tape's), at 64 —
    half a lane group — ``xla``; either way the output and every gradient
    are the XLA stage's. A wrapper around the attribute (what
    ``benchmark/tools/lfm2_check.py`` installs) runs the same path."""
    from paddle_tpu.text.models import Lfm2ShortConv

    paddle.seed(5)
    layer = Lfm2ShortConv(hidden, 3)
    seq = kernels.gated_conv_tokens(hidden, jnp.float32) + 8
    x = np.random.default_rng(6).standard_normal((2, seq, hidden)).astype(
        np.float32)
    stage = la.gated_short_conv
    results = {}
    for name, installed in (
            ("layer", stage),
            ("wrapped", lambda bcu, w: stage(bcu, w)),
            ("xla", lambda bcu, w: stage(bcu, w, kernel=None))):
        dispatch.evict_ops("gated_short_conv")
        before = {p: la._SHORTCONV_TOTAL.value(path=p)
                  for p in ("kernel", "xla")}
        la.gated_short_conv = installed
        try:
            layer.clear_gradients()
            given = paddle.to_tensor(x, stop_gradient=False)
            out = layer(given)
            (out * out).sum().backward()
        finally:
            la.gated_short_conv = stage
        counted = {p: la._SHORTCONV_TOTAL.value(path=p) - n
                   for p, n in before.items()}
        other = "xla" if path == "kernel" else "kernel"
        if name == "xla":
            assert counted == {"kernel": 0, "xla": 0}    # nothing was asked
        else:
            assert counted[path] > 0 and counted[other] == 0, counted
        results[name] = [out._value, given.grad._value] + [
            p.grad._value for p in layer.parameters()]
    dispatch.evict_ops("gated_short_conv")
    assert len(results["layer"]) == 5
    for name in ("layer", "wrapped"):
        for a, b in zip(results[name], results["xla"]):
            close(a, b, 2e-5)
