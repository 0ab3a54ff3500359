"""The router's choice as a stage kernel (``ops/pallas/moe_route.py``), in
the Pallas interpreter on the CPU: ``route_choice`` against
``jax.lax.top_k`` and a gather — ids element for element, exact ties
included, the scores at them bitwise, the loads a bincount, the gradient the
XLA stage's —, ``incubate.moe._route`` the same on its two paths, under
``jax.checkpoint`` too, and a layer's call counted in
``paddle_tpu_moe_route_total{path}``. The decision ``route_path`` by what it
observes: ``tests/test_kernel_placement.py``; that the kernels compile for a
v5e: ``tests/test_mosaic_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate import moe
from paddle_tpu.ops.pallas import moe_route

#: (experts, k) of the expert cells: LFM2, OLMoE, Trinity-Mini, JoyAI and
#: Kimi-Linear, Qwen3-Next, Nemotron
SHAPES = [(32, 4), (64, 8), (128, 8), (256, 8), (512, 10), (512, 22)]
#: a token count that is no multiple of the tile, nor of a lane group
TOKENS = 600


def _scores(experts, k, biased):
    """Sigmoid scores [TOKENS, E] and what the choice is made on — the
    scores plus a bias an expert, or themselves —, with rows of exact ties
    in the latter: all equal, the same value at every third expert above
    the rest, and k equal values at the far end."""
    keys = jax.random.split(jax.random.PRNGKey(experts + k), 2)
    scores = jax.nn.sigmoid(jax.random.normal(keys[0], (TOKENS, experts)))

    def tied(a):
        a = a.at[:5].set(0.5).at[5:10, ::3].set(1.96875)
        return a.at[10:12, -k:].set(1.984375)

    if not biased:
        return tied(scores), tied(scores)
    bias = 0.1 * jax.random.normal(keys[1], (experts,))
    return scores, tied(scores + bias)


@pytest.mark.parametrize("biased", [False, True], ids=["on-scores", "biased"])
@pytest.mark.parametrize("experts, k", SHAPES)
def test_the_choice_is_top_ks_element_for_element(experts, k, biased):
    scores, select = _scores(experts, k, biased)
    topv, topi, n_e = moe_route.route_choice(select, scores, k,
                                             interpret=True)
    want = jax.lax.top_k(select, k)[1]
    assert topi.dtype == jnp.int32 and topv.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want))
    # the tied rows were tied: the lower index first
    assert np.asarray(topi[:5]).tolist() == [list(range(k))] * 5
    np.testing.assert_array_equal(
        np.asarray(topv),
        np.asarray(jnp.take_along_axis(scores, want, axis=1)))
    np.testing.assert_array_equal(
        np.asarray(n_e), np.bincount(np.asarray(want).ravel(),
                                     minlength=experts))


@pytest.mark.parametrize("tokens", [128, 256])
def test_the_tile_is_not_the_answer(tokens):
    """Other tiles, a whole number of them and not: the XLA stage's three
    results."""
    scores, select = (jnp.tile(a, (4, 1)) for a in _scores(256, 8, True))
    for rows in (TOKENS, 2 * tokens):
        got = moe_route.route_choice(select[:rows], scores[:rows], 8,
                                     tokens=tokens, interpret=True)
        for g, w in zip(got, moe._choice(select[:rows], scores[:rows], 8)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("biased", [False, True], ids=["on-scores", "biased"])
@pytest.mark.parametrize("experts, k", SHAPES)
def test_the_gradient_is_the_xla_stages(experts, k, biased):
    """``d scores`` from a cotangent on the chosen scores: the kernel's k
    selects against the XLA stage's transposes (a one-hot product's where
    the choice is biased, ``top_k``'s own where it is not), bitwise — and
    nothing reaches what the choice was made on."""
    scores, _ = _scores(experts, k, biased)
    bias = jnp.linspace(-0.1, 0.1, experts)
    c = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, k))

    def weighted(kernel):
        def fn(scores, bias):
            select = (jax.lax.stop_gradient(scores + bias) if biased
                      else scores)
            return jnp.sum(moe._choice(select, scores, k, kernel)[0] * c)
        return jax.grad(fn, argnums=(0, 1))(scores, bias)

    for got, want in zip(weighted("interpret"), weighted(None)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _router(experts, hidden=32, rows=2, seq=160, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (rows, seq, hidden)),
            jax.random.normal(keys[1], (hidden, experts)) * hidden ** -0.5,
            0.1 * jax.random.normal(keys[2], (experts,)),
            0.1 * jax.random.normal(keys[3], (experts,)))


@pytest.mark.parametrize("renorm", [True, False], ids=["renorm", "raw"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("experts, k", [(128, 8), (512, 22)])
def test_the_router_is_the_same_on_both_paths(experts, k, scoring, renorm):
    """``_route`` end to end, the kernel stage against the XLA stage: the
    weights, the ids, the balancing term, the z-loss and the loads equal,
    and the gradients to the input, the router's weight and its bias from
    all of them."""
    x, w, b, select_bias = _router(experts)
    static = dict(top_k=k, renorm=renorm, scoring=scoring, routed_scale=2.5,
                  counts=True)

    def terms(choice):
        def loss(x, w, b):
            topv, topi, balance, z, n_e = moe._route(
                x, w, b, select_bias if scoring == "sigmoid" else None,
                choice=choice, **static)
            mix = jnp.cos(jnp.arange(topv.size, dtype=jnp.float32)
                          ).reshape(topv.shape)
            return (jnp.sum(topv * mix) + balance + z,
                    (topv, topi, balance, z, n_e))
        (_, outs), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(x, w, b)
        return outs, grads

    (outs, grads), (want_outs, want_grads) = terms("interpret"), terms(None)
    for got, want in zip(outs, want_outs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    assert len(moe._route(x, w, b, choice="interpret",
                          **dict(static, counts=False))) == 4


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_a_checkpoint_around_it_differentiates(scoring):
    """Under ``jax.checkpoint`` (a recomputed block): the forward rule runs
    again inside the backward and the gradient is the plain one's."""
    x, w, b, select_bias = _router(256, seed=3)

    def loss(x, w, choice):
        topv, _, balance, _ = moe._route(
            x, w, b, select_bias if scoring == "sigmoid" else None, top_k=8,
            renorm=True, scoring=scoring, choice=choice)
        return jnp.sum(jnp.square(topv)) + balance

    plain = jax.grad(loss, argnums=(0, 1))(x, w, None)
    kept = jax.jit(jax.grad(jax.checkpoint(
        lambda x, w: loss(x, w, "interpret")), argnums=(0, 1)))(x, w)
    for got, want in zip(kept, plain):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["sorted", "held"])
def test_the_layer_counts_its_path_and_moves_its_bias_alike(held):
    """A sigmoid-routed layer of 128 experts in training mode under the
    interpreter flag takes the kernel stage (counted, one a call; the XLA
    stage with the flag off), its output is the XLA stage's, and the
    selection bias moves against the same loads — the stage's own count."""
    def run(interpret):
        paddle.seed(5)
        layer = moe.MoELayer(32, 16, num_experts=128, top_k=8,
                             scoring="sigmoid", select_bias=True,
                             bias_update_speed=1e-3, held=held,
                             held_rows_factor=64.0, activation="swiglu")
        layer.train()
        x = paddle.to_tensor(np.random.RandomState(1).randn(
            2, 288, 32).astype(np.float32))
        before = {p: moe._ROUTE_TOTAL.value(path=p)
                  for p in ("kernel", "xla")}
        paddle.set_flags({"pallas_interpret": interpret})
        try:
            out = layer(x)
        finally:
            paddle.set_flags({"pallas_interpret": False})
        counted = {p: moe._ROUTE_TOTAL.value(path=p) - n
                   for p, n in before.items()}
        return (np.asarray(out._value),
                np.asarray(layer.e_score_correction_bias._value), counted)

    out, bias, counted = run(True)
    want_out, want_bias, want_counted = run(False)
    assert counted == {"kernel": 1, "xla": 0}
    assert want_counted == {"kernel": 0, "xla": 1}
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-6)
    assert np.abs(bias).max() == pytest.approx(1e-3)
    np.testing.assert_array_equal(bias, want_bias)
