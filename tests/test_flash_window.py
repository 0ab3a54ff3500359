"""The streaming flash kernel under a sliding window (``mha(window=)``: the
banded calls whose grids hold the band's blocks alone) against a ``jnp``
band mask, in the Pallas interpreter on the CPU: outputs and all three
gradients for windows smaller than a block, not a multiple of a block, equal
to and longer than the sequence (the last two ARE the causal kernel, bit for
bit), with and without dropout's mask, the one-pass and the two-call
backward, under ``on_mesh`` on a dp2 x mp2 host mesh; the gate with a
window, its counter, and the band the ``xla`` route builds.
tests/test_mosaic_compile.py compiles the same calls with Mosaic at the
Trinity-Mini cell's shape."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch
from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import flash_attention as fa

mha = functools.partial(fa.mha, interpret=True, causal=True)


def _visible(seq, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    return (j <= i) & (j > i - window)


def _ref(q, k, v, window, keep=None, p_drop=0.0):
    """Softmax attention under an explicit [queries, keys] band mask; with
    ``keep`` the kernel's own dropout mask on the probabilities."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.asarray(_visible(q.shape[2], window)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = jnp.where(jnp.asarray(keep), p / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(seed, b=1, h=2, s=256, d=32, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, h, s, d), dtype) for _ in range(3))


def _grads(fn, q, k, v):
    weight = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
                    argnums=(0, 1, 2))(q, k, v)


#: (seq, window, block_q, block_k): a window smaller than a block, one that
#: is no multiple of a block, one block, one key, blocks of two sizes either
#: way, and a band that reaches back to key 0 from the last query block
BANDS = [(256, 40, 64, 64), (256, 100, 64, 64), (256, 64, 64, 64),
         (256, 1, 64, 64), (256, 100, 64, 32), (256, 72, 32, 64),
         (512, 130, 128, 64), (256, 255, 64, 64)]


@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one-pass", "two-call"])
@pytest.mark.parametrize("seq,window,bq,bk", BANDS)
def test_band_matches_a_jnp_band_mask(seq, window, bq, bk, one_pass,
                                      monkeypatch):
    """Forward and dq, dk, dv of the banded calls, by both backwards: the
    one-pass call (a q block's dq rows zeroed at the first k block of ITS
    band, written at the last) and the two calls past the slab's budget."""
    if not one_pass:
        monkeypatch.setattr(fa, "_ONE_PASS_SLAB_BUDGET", 0)
    q, k, v = _qkv(seq + window, s=seq)
    fn = functools.partial(mha, window=window, block_q=bq, block_k=bk)
    before = fa._BACKWARD_TOTAL.value(calls="one" if one_pass else "two")
    np.testing.assert_allclose(fn(q, k, v), _ref(q, k, v, window),
                               rtol=2e-5, atol=2e-6)
    for got, want in zip(_grads(fn, q, k, v),
                         _grads(lambda *a: _ref(*a, window), q, k, v)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-6)
    assert fa._BACKWARD_TOTAL.value(
        calls="one" if one_pass else "two") == before + 1


@pytest.mark.parametrize("window", [256, 257, 4096])
def test_a_window_of_the_whole_sequence_is_the_causal_kernel(window):
    """``window >= seq`` takes the causal kernel's own calls: the same bits,
    forward and backward, and the same traced program."""
    q, k, v = _qkv(3)
    kw = dict(block_q=64, block_k=64)
    np.testing.assert_array_equal(mha(q, k, v, window=window, **kw),
                                  mha(q, k, v, **kw))
    for got, want in zip(
            _grads(functools.partial(mha, window=window, **kw), q, k, v),
            _grads(functools.partial(mha, **kw), q, k, v)):
        np.testing.assert_array_equal(got, want)
    text = str(jax.make_jaxpr(functools.partial(mha, window=window, **kw))(
        q, k, v))
    assert "flash_stream_fwd" in text and "flash_band" not in text


def test_a_band_has_calls_and_grids_of_its_own():
    """A banded call is told from a full one by name, and its grid's inner
    dimension is the band's block count, not the sequence's: at 64-wide
    blocks a 100-key window meets 3 k blocks of a 16-block row (the k
    blocks a q block's band holds; the dk/dv grid mirrors it)."""
    q, k, v = _qkv(4, s=1024)
    fn = functools.partial(mha, window=100, block_q=64, block_k=64)
    text = str(jax.make_jaxpr(lambda *a: _grads(fn, *a))(q, k, v))
    assert "flash_band_fwd" in text and "flash_band_bwd_dkv_dq" in text
    assert "flash_stream_" not in text
    assert text.count("grid=(2, 16, 3)") == 2       # forward and backward
    assert "grid=(2, 16, 16)" not in text
    # the blocks that hold a band, from the single source of gates and
    # index maps: the lower edge's twins beside the causal edge's
    for i in range(16):
        live = [j for j in range(16)
                if _visible(1024, 100)[i * 64:(i + 1) * 64,
                                       j * 64:(j + 1) * 64].any()]
        assert fa._band_first_kb(i, 64, 64, 100, 16) == live[0]
        assert fa._causal_last_kb(i, 64, 64, 0, 16) == live[-1]
        seen_by = [j for j in range(16)
                   if _visible(1024, 100)[j * 64:(j + 1) * 64,
                                          i * 64:(i + 1) * 64].any()]
        assert fa._causal_first_qb(i, 64, 64, 0, 16) == seen_by[0]
        assert fa._band_last_qb(i, 64, 64, 100, 16) == seen_by[-1]


@pytest.mark.parametrize("seq,window", [(16384, 2048), (256, 40), (64, 64),
                                        (64, 100), (8, 1)])
def test_band_pairs_against_a_count(seq, window):
    want = sum(min(i + 1, window) for i in range(seq))
    assert fa.band_pairs(seq, window) == want
    if seq <= 256:
        assert want == int(_visible(seq, window).sum())


def test_a_banded_call_states_the_bands_cost():
    """The cost estimates count the band's pairs (forward 2 products a pair,
    the one-pass backward 5), not the causal triangle's."""
    q, k, v = _qkv(5, s=1024, d=32)
    fn = functools.partial(mha, window=100, block_q=64, block_k=64)
    jaxpr = jax.make_jaxpr(lambda *a: _grads(fn, *a))(q, k, v)
    costs = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                costs[eqn.params["name"]] = eqn.params["cost_estimate"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    pairs = 2 * fa.band_pairs(1024, 100)
    assert costs["flash_band_fwd"].flops == 2 * pairs * 64
    assert costs["flash_band_bwd_dkv_dq"].flops == 2 * pairs * 160
    assert costs["flash_band_fwd"].transcendentals == pairs


def _keep(seed, bh, seq, p_drop):
    from test_pallas_kernels import _hash_keep_np

    return _hash_keep_np(seed, np.arange(bh).reshape(1, bh, 1, 1),
                         np.arange(seq).reshape(1, 1, seq, 1),
                         np.arange(seq).reshape(1, 1, 1, seq), seq, seq,
                         p_drop)


@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one-pass", "two-call"])
def test_band_with_dropouts_mask(one_pass, monkeypatch):
    """Dropout as in the full kernel: the mask hashes an element's (row,
    column) in the whole score matrix, so a banded call draws the entries
    the full one would."""
    if not one_pass:
        monkeypatch.setattr(fa, "_ONE_PASS_SLAB_BUDGET", 0)
    q, k, v = _qkv(6, s=128, d=16)
    p_drop, seed, window = 0.2, 4321, 50
    fn = functools.partial(mha, window=window, dropout_p=p_drop,
                           seed=jnp.int32(seed), block_q=32, block_k=32)
    keep = _keep(seed, 2, 128, p_drop)

    def ref(q, k, v):
        return _ref(q, k, v, window, keep, p_drop)

    np.testing.assert_allclose(fn(q, k, v), ref(q, k, v), rtol=2e-4,
                               atol=2e-5)
    for got, want in zip(_grads(fn, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
    assert not np.allclose(fn(q, k, v), mha(q, k, v, window=window,
                                            block_q=32, block_k=32))


@pytest.mark.parametrize("kwargs,why", [
    (dict(causal=False, window=8), "causal"),
    (dict(causal=True, window=0), "window"),
])
def test_a_window_takes_causal_self_attention(kwargs, why):
    q, k, v = _qkv(7, s=64)
    with pytest.raises(ValueError, match="a window"):
        fa.mha(q, k, v, interpret=True, **kwargs)
    with pytest.raises(ValueError, match="a window"):
        fa.mha(q, k[:, :, :32], v[:, :, :32], interpret=True, causal=True,
               window=8)


# --------------------------------------------------------------- the gate
@pytest.fixture
def kernels():
    dispatch.evict_ops("flash_attention")
    dispatch.evict_ops("sdpa")
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 128})
    yield
    paddle.set_flags({"pallas_interpret": False,
                      "pallas_attention_min_seq": 1024})
    dispatch.evict_ops("flash_attention")
    dispatch.evict_ops("sdpa")


def _sdpa(q, k, v, **kw):
    return attention.scaled_dot_product_attention(
        paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v), is_causal=True,
        **kw)._value


def test_the_gate_keeps_its_route_under_a_window(kernels):
    """A window changes no decision of the gate: the same shapes take the
    same route, ``stream`` runs the banded kernel and counts the windowed
    call by route beside the route counter."""
    route = functools.partial(
        attention.attention_route, batch=1, num_heads=2, head_dim=32,
        dtype=jnp.float32, packed=False, masked=False, is_causal=True)
    assert route(seq_q=256, seq_k=256, window=40) == "stream"
    assert route(seq_q=64, seq_k=64, window=40) == "xla"
    assert attention.attention_route(
        batch=1, seq_q=256, seq_k=256, num_heads=2, head_dim=32,
        dtype=jnp.float32, packed=False, masked=True, is_causal=True,
        window=40) == "xla"
    for bad in (dict(is_causal=False), dict(seq_q=128), dict(window=0)):
        args = dict(batch=1, seq_q=256, seq_k=256, num_heads=2, head_dim=32,
                    dtype=jnp.float32, packed=False, masked=False,
                    is_causal=True, window=40)
        with pytest.raises(ValueError, match="a window"):
            attention.attention_route(**dict(args, **bad))
    q, k, v = _qkv(8)
    stream = attention._WINDOW_ROUTE_TOTAL.value(route="stream")
    routed = attention._ROUTE_TOTAL.value(route="stream")
    out = _sdpa(q, k, v, window=40)
    np.testing.assert_allclose(out, _ref(q, k, v, 40), rtol=2e-5, atol=2e-6)
    assert attention._WINDOW_ROUTE_TOTAL.value(route="stream") == stream + 1
    assert attention._ROUTE_TOTAL.value(route="stream") == routed + 1
    # a window of the whole sequence is plain causal attention: no windowed
    # call is counted, and the call is the causal one's cached trace
    np.testing.assert_array_equal(_sdpa(q, k, v, window=256),
                                  _sdpa(q, k, v))
    assert attention._WINDOW_ROUTE_TOTAL.value(route="stream") == stream + 1


def test_the_xla_route_builds_the_band_itself():
    """Off the kernels the band is built inside ``_sdpa_ref``, not handed in
    as an ``attn_mask``: the call stays unmasked for the gate."""
    dispatch.evict_ops("sdpa")
    q, k, v = _qkv(9, s=64)
    before = attention._WINDOW_ROUTE_TOTAL.value(route="xla")
    np.testing.assert_allclose(_sdpa(q, k, v, window=10),
                               _ref(q, k, v, 10), rtol=2e-5, atol=2e-6)
    assert attention._WINDOW_ROUTE_TOTAL.value(route="xla") == before + 1
    got = _grads(lambda *a: _sdpa(*a, window=10), q, k, v)
    for a, b in zip(got, _grads(lambda *a: _ref(*a, 10), q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=5e-6)
    from paddle_tpu.nn import functional as F

    np.testing.assert_array_equal(
        F.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=True, window=10)._value, _sdpa(q, k, v, window=10))


def _scaled(q, k, v, scale, window=None):
    """Softmax attention at ``scale`` under an explicit causal (banded)
    mask."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(jnp.asarray(_visible(q.shape[2], window or q.shape[2])),
                  s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("route, seq, window", [
    ("xla", 64, None), ("xla", 64, 10), ("stream", 256, None),
    ("stream", 256, 40)], ids=["xla", "xla-banded", "stream", "banded"])
def test_a_scale_reaches_every_route(kernels, route, seq, window):
    """``scaled_dot_product_attention(scale=)``: Granite-4.0-H's scores are
    at 1/64 where ``head_dim ** -0.5`` is 1/8. On XLA's route, on the
    streaming kernel and on its banded calls the scores take the scale
    given, forward and backward, and the nn.functional entry hands it on."""
    q, k, v = _qkv(11, s=seq, d=64)
    q = q * 4
    scale = 1 / 64
    kw = {} if window is None else {"window": window}
    before = attention._ROUTE_TOTAL.value(route=route)
    out = _sdpa(q, k, v, scale=scale, **kw)
    assert attention._ROUTE_TOTAL.value(route=route) == before + 1
    want = _scaled(q, k, v, scale, window)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    # 1/8 is another function
    assert float(jnp.abs(_sdpa(q, k, v, **kw) - want).max()) > 1e-2
    got = _grads(lambda *a: _sdpa(*a, scale=scale, **kw), q, k, v)
    for a, b in zip(got, _grads(
            lambda *a: _scaled(*a, scale, window), q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=5e-6)
    from paddle_tpu.nn import functional as F

    np.testing.assert_array_equal(
        F.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=True, scale=scale, **kw)._value, out)


@pytest.mark.parametrize("seq", [64, 256], ids=["xla", "stream"])
def test_a_call_without_a_scale_lowers_to_what_it_did(kernels, seq):
    """No ``scale`` is ``scale=None`` is ``head_dim ** -0.5``: the same
    static arguments reach the op (the dispatch cache's key), and the same
    lowered text; another scale is another program."""
    q, k, v = _qkv(12, s=seq)

    def text(**kw):
        return jax.jit(lambda q, k, v: _sdpa(q, k, v, **kw)).lower(
            q, k, v).as_text()

    assert text() == text(scale=None) == text(scale=32 ** -0.5)
    assert text(scale=1 / 32) != text()
    seen = []
    apply_op = attention.apply_op
    attention.apply_op = lambda name, fn, *args, **kw: (
        seen.append((name, kw["scale"], sorted(kw))),
        apply_op(name, fn, *args, **kw))[1]
    try:
        _sdpa(q, k, v)
    finally:
        attention.apply_op = apply_op
    name = "sdpa" if seq == 64 else "flash_attention"
    statics = {"sdpa": ["dropout_p", "is_causal", "scale"],
               "flash_attention": ["dropout_p", "interpret", "is_causal",
                                   "scale"]}[name]
    assert seen == [(name, 1.0 / np.sqrt(32), statics)]


def test_band_shards_over_an_announced_mesh(kernels):
    """Under ``on_mesh`` on a dp2 x mp2 host mesh the banded kernel runs a
    shard a device (batch over dp, heads over mp) and gives the unsharded
    call's outputs and gradients."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import topology

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 2, 128, 16), jnp.float32)
               for _ in range(3))

    def loss(q, k, v):
        out = _sdpa(q, k, v, window=40, training=True)
        return jnp.sum(jnp.sin(out)), out

    grad = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)
    g_ref, o_ref = jax.jit(grad)(q, k, v)
    np.testing.assert_allclose(o_ref, _ref(q, k, v, 40), rtol=2e-5,
                               atol=2e-6)
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    sh = NamedSharding(mesh, P("dp"))

    def on_mesh(q, k, v):
        with topology.tracing_for(mesh):
            return grad(q, k, v)

    g, o = jax.jit(on_mesh, in_shardings=(sh, sh, sh))(q, k, v)
    assert o.sharding.spec == P("dp", "mp")
    np.testing.assert_allclose(o, o_ref, rtol=1e-6, atol=1e-6)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
