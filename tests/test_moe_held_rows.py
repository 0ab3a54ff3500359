"""The held expert path's token-side sums on the CPU (``incubate/moe.py``:
``_held_experts`` + ``_combine(held=True)``, which sum the computed rows
into tokens — ``_rows_to_tokens``) against a plain dense formulation (every
held expert on every token, masked to the pairs that chose it, weighted and
summed): outputs and the gradients to ``x``, the expert weights and the
pairs' weights, in float32 and bf16, at the held shares the four cells run
(1/8, 1/16 with k = 10, 1/32); a token with every choice held, tokens with
none, a batch with no held pair at all; a forced overflow whose OUTPUT is
the reference's under the same row bound; and a guard that no array of N*k
rows of H exists in the layer's forward + backward."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology
from paddle_tpu.incubate import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOKENS, HIDDEN, WIDTH = 96, 32, 16
#: (k, experts, held): the shares of the Trinity-Mini, Qwen3-Next / JoyAI
#: and Kimi-Linear cells
SHARES = {"k8-1of8": (8, 64, 8), "k10-1of16": (10, 64, 4),
          "k8-1of32": (8, 64, 2)}
#: float32: both sides sum the same float32 terms in another order; bf16:
#: the experts' matmuls round to bf16 on both sides, the sums stay float32
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


def _draw(seed, k, experts, count, dtype, first=8):
    rng = np.random.default_rng(seed)
    topi = np.stack([rng.permutation(experts)[:k] for _ in range(TOKENS)])
    x = rng.standard_normal((1, TOKENS, HIDDEN))
    weights = [rng.standard_normal(s) * 0.3 for s in (
        (count, HIDDEN, WIDTH), (count, HIDDEN, WIDTH),
        (count, WIDTH, HIDDEN))]
    topv = rng.uniform(0.1, 1.0, (TOKENS, k))
    return (jnp.asarray(topi, jnp.int32), jnp.asarray(x, dtype),
            [jnp.asarray(w, dtype) for w in weights],
            jnp.asarray(topv, jnp.float32), first)


def _rows_for(topi, first, count, spare=5):
    """A row buffer that every held pair fits, with rows to spare that no
    pair fills (a multiple of 8, as the row tile makes it)."""
    held = int(((np.asarray(topi) >= first)
                & (np.asarray(topi) < first + count)).sum())
    return -(-(held + spare) // 8) * 8, held


def held_path(x, weights, topv, topi, first, rows):
    ys, taken, inv, overflow = moe._held_experts(
        x, topi, *weights, first=first, rows=rows)
    return moe._combine(ys, topv, taken, inv, shape=x.shape,
                        held=True), overflow


def dense(x, weights, topv, topi, first):
    """Every held expert on every token; a token's weight for an expert is
    its pairs' that chose it (float32 sum over the held experts)."""
    w_gate, w_up, w_down = weights
    count = w_up.shape[0]
    rows = x.reshape(-1, x.shape[-1])
    chose = jax.nn.one_hot(topi - first, count, dtype=jnp.float32)
    weight = jnp.sum(chose * topv[:, :, None], axis=1)          # [N, count]
    out = jnp.zeros(rows.shape, jnp.float32)
    for e in range(count):
        mid = (jax.nn.silu((rows @ w_gate[e]).astype(jnp.float32))
               * (rows @ w_up[e]).astype(jnp.float32)).astype(rows.dtype)
        out = out + weight[:, e, None] * (mid @ w_down[e]).astype(
            jnp.float32)
    return out.reshape(x.shape)


def _close(got, want, tolerance, what):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tolerance * max(scale, 1e-6), what


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("share", list(SHARES))
def test_outputs_match_the_dense_formulation(share, dtype):
    k, experts, count = SHARES[share]
    topi, x, weights, topv, first = _draw(3, k, experts, count, dtype)
    rows, held = _rows_for(topi, first, count)
    assert 0 < held < rows < TOKENS * k
    got, overflow = held_path(x, weights, topv, topi, first, rows)
    assert int(overflow) == 0 and got.dtype == jnp.float32
    _close(got, dense(x, weights, topv, topi, first), TOLERANCE[dtype],
           share)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("share", list(SHARES))
def test_gradients_match_the_dense_formulation(share, dtype):
    """To ``x``, to the three expert matrices and to the pairs' weights: the
    dispatch gather's gradient and the weighted sum's are both taken from
    the computed rows."""
    k, experts, count = SHARES[share]
    topi, x, weights, topv, first = _draw(4, k, experts, count, dtype)
    rows, _ = _rows_for(topi, first, count)
    cotangent = jnp.asarray(np.random.default_rng(5).standard_normal(
        x.shape), jnp.float32)

    def through(fn):
        return jax.grad(lambda x, weights, topv: jnp.sum(
            fn(x, weights, topv) * cotangent), argnums=(0, 1, 2))(
                x, weights, topv)

    got = through(lambda x, w, v: held_path(x, w, v, topi, first, rows)[0])
    want = through(lambda x, w, v: dense(x, w, v, topi, first))
    names = ["x", "w_gate", "w_up", "w_down", "topv"]
    for name, a, b in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        _close(a, b, TOLERANCE[dtype], (share, name))
    # a pair that is not held has no part in the output
    absent = (np.asarray(topi) < first) | (np.asarray(topi) >= first + count)
    assert not np.asarray(got[2])[absent].any()


def _by_hand(kind):
    """Expert ids [N, 8] over 64 experts with 8 held from 8 on."""
    rng = np.random.default_rng(6)
    elsewhere = np.concatenate([np.arange(8), np.arange(16, 64)])
    topi = np.stack([rng.permutation(elsewhere)[:8] for _ in range(TOKENS)])
    if kind == "one-token-holds-all-k":
        topi[17] = rng.permutation(np.arange(8, 16))
    elif kind == "every-other-token-holds-all-k":
        for t in range(0, TOKENS, 2):
            topi[t] = rng.permutation(np.arange(8, 16))
    elif kind == "last-token-alone":
        topi[-1, 3] = 11
    elif kind == "first-and-last-pair":
        topi[0, 0], topi[-1, -1] = 8, 15
    else:
        assert kind == "no-held-pair"
    return jnp.asarray(topi, jnp.int32)


@pytest.mark.parametrize("kind", [
    "one-token-holds-all-k", "every-other-token-holds-all-k",
    "last-token-alone", "first-and-last-pair", "no-held-pair"])
def test_tokens_with_all_their_choices_held_and_tokens_with_none(kind):
    _, x, weights, topv, first = _draw(7, 8, 64, 8, jnp.float32)
    topi = _by_hand(kind)
    rows, held = _rows_for(topi, first, 8)
    cotangent = jnp.asarray(np.random.default_rng(8).standard_normal(
        x.shape), jnp.float32)

    def value_and_grads(fn):
        return jax.value_and_grad(lambda x, weights, topv: jnp.sum(
            fn(x, weights, topv) * cotangent), argnums=(0, 1, 2))(
                x, weights, topv)

    out, overflow = held_path(x, weights, topv, topi, first, rows)
    assert int(overflow) == 0
    want = dense(x, weights, topv, topi, first)
    _close(out, want, 1e-5, kind)
    empty = ~((np.asarray(topi) >= 8) & (np.asarray(topi) < 16)).any(axis=1)
    assert not np.asarray(out)[0, empty].any()      # zero, not nearly zero
    assert held == 0 or np.asarray(out)[0, ~empty].all(axis=-1).any()
    got = value_and_grads(
        lambda x, w, v: held_path(x, w, v, topi, first, rows)[0])
    ref = value_and_grads(lambda x, w, v: dense(x, w, v, topi, first))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        _close(a, b, 1e-5, kind)
        assert np.isfinite(np.asarray(a)).all()


@pytest.mark.parametrize("share", list(SHARES))
def test_an_overflow_keeps_the_first_rows_in_expert_token_choice_order(share):
    """The row bound in the reference's words: held pairs in (expert, token,
    choice) order; a pair whose rank reaches ``rows`` is dropped. The
    dense formulation masked by that rule gives the same output, and
    ``held_overflow`` counts the rest to the unit."""
    k, experts, count = SHARES[share]
    topi, x, weights, topv, first = _draw(9, k, experts, count, jnp.float32)
    _, held = _rows_for(topi, first, count)
    rows = (held // 2) // 8 * 8
    assert 0 < rows < held
    got, overflow = held_path(x, weights, topv, topi, first, rows)
    assert int(overflow) == held - rows
    here = np.asarray(jax.nn.one_hot(topi - first, count)).sum(axis=1)
    per_expert = here.sum(axis=0)
    rank = (np.cumsum(per_expert) - per_expert)[None] + (
        np.cumsum(here, axis=0) - here)
    kept = (here > 0) & (rank < rows)                           # [N, count]
    keep_pair = np.take_along_axis(
        np.pad(kept, ((0, 0), (0, 1))),
        np.where((np.asarray(topi) >= first)
                 & (np.asarray(topi) < first + count),
                 np.asarray(topi) - first, count), axis=1)
    assert int(keep_pair.sum()) == rows
    want = dense(x, weights, topv * jnp.asarray(keep_pair, jnp.float32),
                 topi, first)
    _close(got, want, 1e-5, share)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references", "joyai-llm-flash.py")
    spec = importlib.util.spec_from_file_location("joyai_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _layer(factor):
    paddle.seed(13)
    layer = moe.MoELayer(
        64, 32, 32, top_k=4, activation="swiglu", gate_bias=False,
        norm_topk_prob=True, scoring="sigmoid", select_bias=True,
        routed_scale=2.5, shared_width=32, aux_weight=0.0, held=(8, 8),
        held_rows_factor=factor)
    layer.e_score_correction_bias.set_value(np.random.default_rng(
        14).normal(0, 0.02, 32).astype(np.float32))
    return layer


@pytest.mark.parametrize("factor", [0.5, 8.0], ids=["overflow", "fits"])
def test_the_layer_under_a_forced_overflow_gives_the_references_output(
        reference, factor):
    """2,048 tokens, 4 of 32 experts a token, 8 held: 2,048 pairs on the
    mean, and at factor 0.5 a buffer of 1,024 rows on both sides (the
    512-row tile). The layer's OUTPUT, not only its count, is what
    ``benchmark/references/joyai-llm-flash.py experts()`` gives under the
    same bound."""
    layer = _layer(factor)
    layer.train()
    sizes = {"num_experts_per_tok": 4, "router_experts": 32,
             "held_experts": [8, 8], "norm_topk_prob": True,
             "routed_scaling_factor": 2.5, "held_rows_factor": factor}
    x = np.random.default_rng(15).standard_normal((2, 1024, 64)).astype(
        np.float32)
    rows = moe.held_rows(2048, 4, 8, 32, factor)
    assert rows == reference.held_rows(2048, sizes) == (
        1024 if factor == 0.5 else 8192)
    state = layer.functional_state()
    w = {k: jnp.asarray(v) for tree in state for k, v in tree.items()}
    want, _, _, dropped, landed = reference.experts(
        w, jnp.asarray(x).reshape(2048, 64), sizes, "")
    assert int(dropped) == max(int(landed) - rows, 0)
    assert (int(dropped) > 500) == (factor == 0.5)
    got = np.asarray(layer(paddle.to_tensor(x))._value)
    assert int(layer.held_overflow._value) == int(dropped)
    _close(got, np.asarray(want).reshape(x.shape), 2e-5, factor)


def _avals(jaxpr):
    """Every intermediate's abstract value, through the nested programs
    (custom gradients, jit, remat) of a jaxpr."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield eqn.primitive.name, var.aval
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


def _pair_wide(avals, pairs, hidden):
    """The intermediates that hold a row of ``hidden`` for every (token,
    choice) pair, whatever their rank: [N*k, H], [N, k, H], [B, S, k, H]."""
    return [(name, aval.shape) for name, aval in avals
            if len(getattr(aval, "shape", ())) >= 2
            and aval.shape[-1] == hidden
            and int(np.prod(aval.shape[:-1])) == pairs]


@pytest.mark.parametrize("share", list(SHARES))
def test_no_array_of_every_pairs_row_exists_forward_or_backward(share):
    """The structural guard: in the traced forward + backward of a held
    layer (router, dispatch, experts, weighted sum, shared expert) nothing
    has N*k or N x k rows of H; the widest arrays follow ``rows`` and N."""
    k, experts, count = SHARES[share]
    tokens, hidden, width = 256, 48, 24
    paddle.seed(16)
    layer = moe.MoELayer(
        hidden, width, experts, top_k=k, activation="swiglu",
        gate_bias=False, scoring="sigmoid", select_bias=True,
        shared_width=width, held=(8, count), held_rows_factor=2.0)
    layer.train()
    rows = moe.held_rows(tokens, k, count, experts, 2.0)
    assert rows + k - 1 < tokens * k and rows not in (tokens, tokens * k)
    params, buffers = layer.functional_state()
    x = jnp.asarray(np.random.default_rng(17).standard_normal(
        (2, tokens // 2, hidden)), jnp.float32)

    def loss(params, x):
        saved = layer.functional_state()
        try:
            with dispatch.trace_mode():
                layer.load_functional_state(params, buffers)
                return jnp.sum(layer(Tensor(x))._value ** 2)
        finally:
            layer.load_functional_state(*saved)

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    avals = list(_avals(traced.jaxpr))
    assert not _pair_wide(avals, tokens * k, hidden)
    # the guard sees what it looks for: the dropless path of the same
    # layer computes every pair, so it has such arrays
    every = moe.MoELayer(hidden, width, experts, top_k=k,
                         activation="swiglu", gate_bias=False)
    state = every.functional_state()

    def dropless(params, x):
        try:
            with dispatch.trace_mode():
                every.load_functional_state(params, state[1])
                return jnp.sum(every(Tensor(x))._value ** 2)
        finally:
            every.load_functional_state(*state)

    assert _pair_wide(_avals(jax.make_jaxpr(jax.grad(dropless))(
        state[0], x).jaxpr), tokens * k, hidden)
    # and the rows' arrays are there: the path did run
    assert any(getattr(a, "shape", ()) == (rows, hidden) for _, a in avals)
