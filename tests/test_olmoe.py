"""OLMoE on the CPU at a small size (hidden 64, 4 heads of 16, 8 experts
top-2 of width 32, 2 layers, seq 32, seeded random weights): the framework
model against the plain reference (benchmark/references/olmoe-1b-7b.py:
every expert on every token, no sort, nothing imported from paddle_tpu),
the dropless expert path against an every-expert dense computation under
any imbalance, the shape of the traced program, and the two counters a
benchmark cell reads. The same comparison runs at published widths on the
chip (benchmark/configs/olmoe-1b-7b.py check_train)."""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate import moe
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.aux_loss import collect_aux_losses, total_aux_loss
from paddle_tpu.ops import attention
from paddle_tpu.text.models import OlmoeModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "intermediate_size": 32, "num_experts": 8,
         "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "norm_topk_prob": False, "router_aux_loss_coef": 0.01,
         "router_z_loss_coef": 0.001}
ROWS, SEQ = 2, 32

# Both sides compute the same equations in float32 on the CPU and differ in
# summation order only (the grouped matmul, the chunked loss, the fused
# softmax): errors stay at a few float32 roundings (1e-7 relative to the
# largest value) through two layers. A bf16 computation is off by 1e-3 and
# more, a wrong permutation or a dropped token by O(1).
RTOL = 2e-5
# gradients sum 64 token contributions and pass through two layers'
# softmaxes; compared against the largest gradient entry of each parameter
GRAD_RTOL = 1e-4


@pytest.fixture(autouse=True)
def _no_global_mesh():
    """``auto`` reads the global mesh where no step builder announced one;
    a test file run earlier in this worker may have left one with an 'ep'
    axis (tests/test_moe.py does)."""
    from paddle_tpu.distributed import topology

    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references", "olmoe-1b-7b.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    paddle.seed(25)
    net = OlmoeModel(**SIZES)
    # the initialiser's 0.02 leaves every router nearly uniform; widen the
    # routers so that the top-2 are decided by more than rounding
    for layer in net.layers:
        gate = layer.mlp.gate.weight
        gate.set_value(np.asarray(gate._value) * 25.0)
    net.eval()
    return net


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(7).integers(
        0, SIZES["vocab_size"], (ROWS, SEQ)), jnp.int32)


@contextlib.contextmanager
def loaded(net, params):
    saved = net.functional_state()
    try:
        with dispatch.trace_mode():
            net.load_functional_state(params, {})
            yield
    finally:
        net.load_functional_state(*saved)


def framework_terms(net, params, ids):
    """(logits, cross-entropy, weighted auxiliary sum) as a train step
    computes them: the chunked loss on the final hidden states, the
    auxiliary losses through the collector."""
    with loaded(net, params):
        x = Tensor(ids, stop_gradient=True)
        logits = net(x)._value
        with collect_aux_losses() as auxes:
            hidden = net.features(x)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
        ce = F.linear_cross_entropy(hidden, net.lm_head.weight, labels,
                                    chunk_size=16)._value
        return logits, ce, total_aux_loss(auxes)


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_logits_and_the_three_loss_terms_match_the_reference(
        model, reference, ids):
    params = model.functional_state()[0]
    logits, ce, aux = jax.jit(
        lambda p, a: framework_terms(model, p, a))(params, ids)
    ref_logits = reference.forward(params, ids, SIZES)
    total, ref_ce, balance, z = reference.loss_terms(params, ids, SIZES)
    assert rel_err(logits, ref_logits) < RTOL
    assert rel_err(ce, ref_ce) < RTOL
    assert rel_err(ce + aux, total) < RTOL
    # the two auxiliary terms one at a time: the layers' weights are plain
    # attributes read at trace time
    for coefs, want in (((1.0, 0.0), balance), ((0.0, 1.0), z)):
        for layer in model.layers:
            layer.mlp.aux_weight, layer.mlp.z_loss_weight = coefs
        try:
            _, _, term = framework_terms(model, params, ids)
        finally:
            for layer in model.layers:
                layer.mlp.aux_weight, layer.mlp.z_loss_weight = 0.01, 0.001
        assert rel_err(term, want) < RTOL, coefs
    # routing is not degenerate: the balance term of uniform routing is k
    assert float(balance) > SIZES["num_hidden_layers"] * 2 * 1.05


def test_gradients_of_every_parameter_match_the_reference(
        model, reference, ids):
    params = model.functional_state()[0]

    def loss(p):
        _, ce, aux = framework_terms(model, p, ids)
        return ce + aux

    got = jax.jit(jax.grad(loss))(params)
    want = jax.grad(lambda p: reference.loss_terms(p, ids, SIZES)[0])(params)
    assert set(got) == set(want) == set(params)
    for name in params:
        assert float(np.abs(np.asarray(want[name])).max()) > 0, name
        assert rel_err(got[name], want[name]) < GRAD_RTOL, name


# ------------------------------------------------------ the dropless path
def dense_every_expert(x, topv, topi, w_gate, w_up, w_down):
    """Every expert on every token; a token's k weights pick its own."""
    n, h = x.shape[0] * x.shape[1], x.shape[-1]
    xf = x.reshape(n, h)
    every = jnp.einsum(
        "enf,efh->enh",
        jax.nn.silu(jnp.einsum("nh,ehf->enf", xf, w_gate))
        * jnp.einsum("nh,ehf->enf", xf, w_up), w_down)       # [E, N, H]
    picked = every[topi, jnp.arange(n)[:, None]]              # [N, k, H]
    return jnp.einsum("nkh,nk->nh", picked, topv).reshape(x.shape)


def sorted_path(x, topv, topi, w_gate, w_up, w_down, kernel=None):
    ys, order, inv = moe._sorted_experts(x, topi, w_gate, w_up, w_down,
                                         kernel=kernel)
    return moe._combine(ys, topv, order, inv, shape=x.shape)


def _routing(case, n, e, rng):
    if case == "uniform":          # every expert the same number of pairs
        topi = np.stack([np.arange(n) % e, (np.arange(n) + 3) % e], axis=1)
    elif case == "one-expert-empty":
        topi = np.stack([rng.choice([0, 1, 2, 4, 5, 6, 7], 2, replace=False)
                         for _ in range(n)])
    elif case == "all-on-one-expert":
        topi = np.full((n, 1), 5)
    else:                          # drawn: ragged groups
        topi = np.stack([rng.choice(e, 2, replace=False) for _ in range(n)])
    topv = rng.uniform(0.05, 0.6, topi.shape)
    return jnp.asarray(topv, jnp.float32), jnp.asarray(topi, jnp.int32)


@pytest.mark.parametrize("kernel", [None, "interpret"],
                         ids=["xla", "interpret"])
@pytest.mark.parametrize("case", ["uniform", "drawn", "one-expert-empty",
                                  "all-on-one-expert"])
def test_dropless_path_equals_every_expert_dense(case, kernel):
    """Nothing dropped and nothing misplaced under any imbalance: outputs
    and the gradients of inputs, routing weights and every expert's
    weights equal the dense computation's (an expert without tokens gets a
    zero gradient on both sides). Both grouped matmuls: ``ragged_dot`` and
    the megablox kernel (here in the Pallas interpreter)."""
    import functools

    sorted_path = functools.partial(globals()["sorted_path"], kernel=kernel)
    rng = np.random.default_rng(3)
    e, h, f = 8, 16, 24
    x = jnp.asarray(rng.normal(size=(3, 16, h)), jnp.float32)
    topv, topi = _routing(case, 48, e, rng)
    weights = tuple(jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
                    for s in ((e, h, f), (e, h, f), (e, f, h)))
    args = (x, topv, topi) + weights
    np.testing.assert_allclose(sorted_path(*args), dense_every_expert(*args),
                               rtol=1e-5, atol=1e-5)
    target = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def grads(fn):
        return jax.grad(lambda x, v, *w: jnp.sum(fn(x, v, topi, *w) * target),
                        argnums=(0, 1, 2, 3, 4))(x, topv, *weights)

    for got, want in zip(grads(sorted_path), grads(dense_every_expert)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if case == "one-expert-empty":
        assert not np.asarray(grads(sorted_path)[2][3]).any()


def test_auto_layer_is_dropless_and_matches_the_dense_mode():
    """MoELayer's ``auto`` at 8 experts with no 'ep' axis: the sorted path,
    equal to ``dense`` on the same weights (gelu and SwiGLU experts), its
    load-balancing term k times dense's (all k choices counted)."""
    x = paddle.to_tensor(
        np.random.default_rng(0).normal(size=(2, 12, 16)).astype(np.float32))
    for activation in ("gelu", "swiglu"):
        paddle.seed(4)
        kw = dict(num_experts=8, top_k=2, activation=activation,
                  gate_bias=False, norm_topk_prob=False, aux_weight=1.0)
        auto = moe.MoELayer(16, 24, **kw)
        dense = moe.MoELayer(16, 24, dispatch_mode="dense", **kw)
        dense.set_state_dict(auto.state_dict())
        assert auto.resolved_mode() == "sorted"
        np.testing.assert_allclose(auto(x).numpy(), dense(x).numpy(),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(auto.aux_loss.numpy()),
                                   2 * float(dense.aux_loss.numpy()),
                                   rtol=1e-5)


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_traced_layer_holds_no_tokens_by_experts_by_capacity_array():
    """The capacity path's dispatch tensor is [N, E, C]; the sorted path's
    largest array is the [N k, H] gathered rows."""
    n, e, k, h, f = 512, 8, 2, 32, 16
    cap = int(np.ceil(1.25 * k * n / e))
    paddle.seed(1)
    kw = dict(num_experts=e, top_k=k, activation="swiglu", gate_bias=False)
    x = jnp.zeros((4, n // 4, h), jnp.float32)

    def largest(layer):
        def fn(p, a):
            with dispatch.trace_mode():
                layer.load_functional_state(p, {})
                return layer(Tensor(a))._value

        params = layer.functional_state()[0]
        try:
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p, a: jnp.sum(fn(p, a))))(params, x)
        finally:
            layer.load_functional_state(params, {})
        return max(int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)
                   if hasattr(a, "shape"))

    assert largest(moe.MoELayer(h, f, **kw)) == n * k * h
    assert largest(moe.MoELayer(h, f, dispatch_mode="capacity", **kw)) \
        >= n * e * cap


def test_counters_sorted_once_a_layer_and_route_stream(model, reference, ids):
    """What a cell reads to show which program it ran: the expert layer's
    path and the attention route, one count a layer a trace. With the
    kernels selected (the interpreter here) and the key-length threshold
    under the sequence, causal attention takes route ``stream`` — and
    still agrees with the reference."""
    params = model.functional_state()[0]

    def counts():
        return (moe._DISPATCH_TOTAL.value(path="sorted"),
                attention._ROUTE_TOTAL.value(route="stream"),
                attention._ROUTE_TOTAL.value(route="xla"))

    def logits(p, a):
        with loaded(model, p):
            return model(Tensor(a, stop_gradient=True))._value

    layers = SIZES["num_hidden_layers"]
    before = counts()
    jax.jit(logits)(params, ids)
    after = counts()
    assert after[0] - before[0] == layers
    assert after[1] == before[1] and after[2] - before[2] == layers
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": SEQ})
    try:
        # a new function object: the flags are read at trace time
        streamed = jax.jit(lambda p, a: logits(p, a))(params, ids)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    final = counts()
    assert final[1] - after[1] == layers and final[2] == after[2]
    assert rel_err(streamed, reference.forward(params, ids, SIZES)) < RTOL
