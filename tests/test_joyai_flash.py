"""JoyAI-LLM-Flash on the CPU at a small size (hidden 64, 4 heads with
24-wide keys and 16-wide values, 32 experts top-4 of width 32 with 8 held,
a dense block + 2 expert blocks + the MTP module, seq 32, seeded random
weights): the framework model against the plain reference
(benchmark/references/joyai-llm-flash.py: every held expert on every token,
nothing imported from paddle_tpu), latent attention against a naive
softmax(q k^T) v with the shared rope key, the sigmoid bias-balanced
router against a hand computation, the bias buffer through a train step,
the share test of the model-configs guide, the overflow count, and
recomputation. The same comparison runs at published widths on the chip
(benchmark/configs/joyai-llm-flash.py check_train)."""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.incubate import moe
from paddle_tpu.nn.aux_loss import collect_aux_losses, total_aux_loss
from paddle_tpu.text.models import (JoyAIFlashModel, MLAttention,
                                    mtp_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
         "num_attention_heads": 4, "intermediate_size": 96,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "router_experts": 32, "held_experts": [8, 8],
         "num_experts_per_tok": 4, "n_shared_experts": 1,
         "first_k_dense_replace": 1, "q_lora_rank": 48, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "rms_norm_eps": 1e-6, "rope_theta": 32000000,
         "norm_topk_prob": True, "routed_scaling_factor": 2.5,
         "num_nextn_predict_layers": 1, "bias_update_speed": 0.001,
         "mtp_loss_weight": 0.3, "balance_loss_weight": 1.25e-5,
         "initializer_range": 0.1, "held_rows_factor": 8.0}
ROWS, SEQ = 2, 32

# Both sides compute the same equations in float32 on the CPU and differ in
# summation order only: errors stay at a few float32 roundings through four
# blocks. bf16 arithmetic is off by 1e-3 and more, a wrong permutation, a
# dropped pair or a bias in the weights by O(1).
RTOL = 2e-5
# gradients sum 64 tokens' contributions through four blocks' softmaxes;
# compared against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references", "joyai-llm-flash.py")
    spec = importlib.util.spec_from_file_location("joyai_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_kwargs(sizes=SIZES, **over):
    skip = ("router_experts", "held_experts", "mtp_loss_weight",
            "n_routed_experts")
    kw = {k: v for k, v in sizes.items() if k not in skip}
    kw.update(n_routed_experts=sizes["router_experts"],
              held_experts=tuple(sizes["held_experts"]))
    kw.update(over)
    return kw


def build(seed=29, **over):
    paddle.seed(seed)
    net = JoyAIFlashModel(**model_kwargs(**over))
    rng = np.random.default_rng(seed)
    for _, sub in net.named_sublayers():
        if isinstance(sub, moe.MoELayer):
            # a bias that is not zero, so that it shows where it may not
            sub.e_score_correction_bias.set_value(
                rng.normal(0, 0.02, sub.num_experts).astype(np.float32))
    net.train()
    return net


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(7).integers(
        0, SIZES["vocab_size"], (ROWS, SEQ)), jnp.int32)


@contextlib.contextmanager
def loaded(net, params, buffers=None):
    saved = net.functional_state()
    try:
        with dispatch.trace_mode():
            net.load_functional_state(params, buffers or saved[1])
            yield
    finally:
        net.load_functional_state(*saved)


def framework_terms(net, params, ids, buffers=None):
    """(main logits, total loss, main term, MTP term, buffers afterwards)
    as a train step computes them: both cross-entropies on the final hidden
    states, the balance loss through the collector."""
    with loaded(net, params, buffers):
        x = Tensor(ids, stop_gradient=True)
        with collect_aux_losses() as auxes:
            hidden, mtp_hidden = net.training_features(x)
        # one forward, as in a step: a second one in train mode would see
        # the selection bias the first one moved
        logits = net.lm_head(hidden)._value
        total, main, mtp = mtp_lm_loss(hidden, mtp_hidden, net.lm_head.weight,
                                       x, SIZES["mtp_loss_weight"])
        return (logits, total._value + total_aux_loss(auxes), main._value,
                mtp._value, net.functional_state()[1])


def weights(net):
    params, buffers = net.functional_state()
    return {**params, **buffers}


def test_logits_and_both_loss_terms_match_the_reference(model, reference,
                                                        ids):
    params = model.functional_state()[0]
    logits, total, main, mtp, _ = jax.jit(
        lambda p, a: framework_terms(model, p, a)[:4] + (None,))(params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    scale = float(jnp.abs(ref[0]).max())
    assert float(jnp.abs(logits - ref[0]).max()) <= RTOL * scale
    assert float(ref[6]) == 0            # nothing dropped
    for got, want in ((total, ref[2]), (main, ref[3]), (mtp, ref[4])):
        assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    # the MTP term is a term of its own: another lambda moves the total
    assert abs(float(total) - float(main)) > 0.1


def test_gradients_of_every_parameter_match_the_reference(model, reference,
                                                          ids):
    params, buffers = model.functional_state()
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[1]))(params)
    want = jax.grad(lambda p: reference.loss_terms(
        {**p, **buffers}, ids, SIZES)[0])(params)
    assert set(got) == set(want)
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        err = float(jnp.abs(got[name] - want[name]).max())
        assert err <= GRAD_RTOL * scale, (name, err, scale)


@pytest.mark.parametrize("kernel", [False, True])
def test_latent_attention_matches_naive_softmax_with_the_shared_rope_key(
        reference, kernel):
    """The MLA sublayer alone against the reference's naive form (scores
    from the nope part per head plus the ONE rotated rope key for all
    heads); with ``pallas_interpret`` the core is the streaming kernel at
    24-wide keys and 16-wide values."""
    paddle.seed(3)
    attn = MLAttention(64, 4, 48, 32, 16, 8, 16, rms_norm_eps=1e-6,
                       rope_theta=32000000)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 64, 64)), jnp.float32)
    w = {k: v for k, v in attn.functional_state()[0].items()}
    want = reference.attention(w, x, SIZES, "")
    flags = {"pallas_interpret": True, "pallas_attention_min_seq": 0}
    saved = {k: paddle.get_flags([k])[k] for k in flags}
    if kernel:
        paddle.set_flags(flags)
    try:
        got = attn(paddle.to_tensor(np.asarray(x)))._value
    finally:
        paddle.set_flags(saved)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())


def test_router_bias_moves_the_choice_and_not_the_weight():
    """Against a hand computation: s = sigmoid(logits), top-2 of s + b,
    weights s / sum s x 2.5."""
    x = jnp.asarray([[[1.0, 0.0], [0.0, 1.0]]])                 # 2 tokens
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.0, 1.5]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])

    def route(b):
        return moe._route(x, w, None, b, top_k=2, renorm=True,
                          scoring="sigmoid", routed_scale=2.5)

    s = 1.0 / (1.0 + np.exp(-np.asarray(w)))
    topv, topi, _, _ = route(None)
    assert topi.tolist() == [[0, 1], [3, 2]]
    np.testing.assert_allclose(
        topv[0], 2.5 * s[0, [0, 1]] / s[0, [0, 1]].sum(), rtol=1e-6)
    # the bias lifts expert 2 over expert 1 for token 0 ...
    topv_b, topi_b, _, _ = route(bias)
    assert topi_b.tolist() == [[2, 0], [2, 3]]
    # ... and the weights are the scores WITHOUT it, renormalised, x 2.5
    np.testing.assert_allclose(
        topv_b[0], 2.5 * s[0, [2, 0]] / s[0, [2, 0]].sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(topv_b).sum(axis=1), 2.5,
                               rtol=1e-6)
    # unrenormalised, unscaled: the raw scores
    raw, _, _, _ = moe._route(x, w, None, bias, top_k=2, renorm=False,
                              scoring="sigmoid")
    np.testing.assert_allclose(raw[0], s[0, [2, 0]], rtol=1e-6)


def test_bias_buffer_moves_against_the_load_in_a_train_step(ids):
    """Through ``spmd.build_train_step``: every expert layer's bias rises
    by ``bias_update_speed`` where the step's load was under the mean and
    falls where it was over (the buffer threads through the step, under
    recomputation too), and takes no gradient (it is no parameter)."""
    net = build(use_recompute=True)

    class Wrapper(paddle.nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            hidden, mtp_hidden = self.lm.training_features(x)
            return hidden, tuple(mtp_hidden), self.lm.lm_head.weight

    wrapper = Wrapper(net)
    wrapper.train()
    before = {n: np.asarray(v) for n, v in
              wrapper.functional_state()[1].items()}
    opt = optimizer.AdamW(1e-3, parameters=net.parameters())
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step, init = spmd.build_train_step(
        wrapper, lambda out, y: mtp_lm_loss(out[0], list(out[1]), out[2], y,
                                            0.3)[0]._value,
        opt, mesh=mesh, donate=False)
    params, opt_state = init()
    # the loads the step will see: the reference's router on the same state
    loss, params, opt_state = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))
    after = {n: np.asarray(v) for n, v in
             wrapper.functional_state()[1].items()}
    moved = [n for n in after if n.endswith("e_score_correction_bias")]
    assert len(moved) == 3               # two expert layers + the MTP block
    assert not any("bias" in n for n, _ in wrapper.named_parameters())
    for n in moved:
        delta = after[n] - before[n]
        assert np.isclose(np.abs(delta), 0.001).sum() + (delta == 0).sum() == 32
        # 32 experts, 64 tokens x 4: loads differ, so some rise, some fall
        assert (delta > 0).any() and (delta < 0).any()
        assert abs(float(delta.sum())) < 0.032
    assert all(int(after[n]) == 0 for n in after
               if n.endswith("held_overflow"))


def _expert_layer(held, seed=11, factor=8.0, **over):
    paddle.seed(seed)
    kw = dict(top_k=4, activation="swiglu", gate_bias=False,
              norm_topk_prob=True, scoring="sigmoid", select_bias=True,
              routed_scale=2.5, shared_width=32, aux_weight=0.0,
              held=held, held_rows_factor=factor)
    kw.update(over)
    layer = moe.MoELayer(64, 32, 32, **kw)
    layer.eval()
    return layer


def test_share_test_all_held_ranges_and_the_shared_expert_once(reference):
    """The guide's share test: the routed parts that the 4 ranges of 8 of
    32 experts give, plus the shared expert counted ONCE, add up to what
    the uncut reference gives for the whole layer."""
    whole = _expert_layer(None)
    rng = np.random.default_rng(5)
    whole.e_score_correction_bias.set_value(
        rng.normal(0, 0.02, 32).astype(np.float32))
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)
    state = whole.functional_state()
    w = {k: jnp.asarray(v) for tree in state for k, v in tree.items()}
    sizes = dict(SIZES, held_experts=[0, 32], n_routed_experts=32)
    want, _, _, dropped, landed = reference.experts(
        w, jnp.asarray(x).reshape(32, 64), sizes, "")
    assert int(dropped) == 0 and int(landed) == 32 * 4
    want = want.reshape(2, 16, 64)
    shared = np.asarray(whole.shared(paddle.to_tensor(x))._value)
    total = np.zeros_like(x)
    for first in range(0, 32, 8):
        part = _expert_layer((first, 8))
        part.load_functional_state(
            {n: (v[first:first + 8] if n.startswith("w_") else v)
             for n, v in state[0].items()},
            {"e_score_correction_bias": state[1]["e_score_correction_bias"]})
        assert part.resolved_mode() == "sorted_held"
        out = np.asarray(part(paddle.to_tensor(x))._value)
        total += out - shared            # this range's routed part
    total += shared                      # what every chip computes alike
    assert np.abs(total - np.asarray(want)).max() <= RTOL * np.abs(
        np.asarray(want)).max()
    # and the uncut layer itself agrees with the reference
    full = np.asarray(whole(paddle.to_tensor(x))._value)
    assert np.abs(full - np.asarray(want)).max() <= RTOL * np.abs(
        np.asarray(want)).max()


def test_a_forced_overflow_shows_in_the_count(reference):
    """A row buffer smaller than the pairs that land here drops the rest
    and says how many, in a training step's buffer; the reference, given
    the same rule, gives the same partial result."""
    layer = _expert_layer((8, 8))
    x = np.random.default_rng(9).standard_normal((2, 16, 64)).astype(
        np.float32)
    # at this size the 512-row tile admits every pair (program and
    # reference alike): shrink the tile to force the bound
    assert moe.held_rows(32, 4, 8, 32, 0.5) == 128 == reference.held_rows(
        32, dict(SIZES, held_rows_factor=0.5))
    tight = moe.held_rows(32, 4, 8, 32, 0.5, tile=8)
    assert tight == 16
    topi = jax.lax.top_k(jnp.asarray(
        np.random.default_rng(1).standard_normal((32, 32))), 4)[1]
    here = int(((topi >= 8) & (topi < 16)).sum())
    assert here > tight
    ys, taken, inv, overflow = moe._held_experts(
        jnp.asarray(x), topi.astype(jnp.int32), layer.w_gate._value,
        layer.w_up._value, layer.w_down._value, first=8, rows=tight)
    assert int(overflow) == here - tight
    assert int((inv < tight).sum()) == tight
    # exact when the bound holds
    _, _, inv_all, none = moe._held_experts(
        jnp.asarray(x), topi.astype(jnp.int32), layer.w_gate._value,
        layer.w_up._value, layer.w_down._value, first=8, rows=128)
    assert int(none) == 0 and int((inv_all < 128).sum()) == here


def test_overflow_count_leaves_the_layer_as_a_buffer():
    layer = _expert_layer((8, 8))
    layer.train()
    x = paddle.to_tensor(np.random.default_rng(2).standard_normal(
        (2, 16, 64)).astype(np.float32))
    layer(x)
    assert "held_overflow" in layer.functional_state()[1]
    assert int(layer.held_overflow._value) == 0


@pytest.mark.parametrize("kernels", [False, True])
def test_recomputation_gives_the_same_loss_and_gradients(
        ids, kernels, residual_counts):
    """``kernels``: the streaming kernel on every latent-attention core
    (here in the Pallas interpreter), so each recomputed block keeps the
    kernel's output and log-sum-exp (ops/residuals.py) — four kernel calls
    a traced step (three blocks + the MTP module's), each offering its two
    arrays, kept where ``recompute`` wraps the block and nowhere else."""
    from paddle_tpu.ops import residuals

    plain, remat = build(use_recompute=False), build(use_recompute=True)
    params, buffers = plain.functional_state()

    def loss_and_state(net):
        def fn(p):
            out = framework_terms(net, p, ids, buffers)
            return out[1], out[4]
        before = residual_counts()
        got = jax.jit(jax.value_and_grad(fn, has_aux=True))(params)
        calls, kept = (4, 4 * (net is remat)) if kernels else (0, 0)
        assert residual_counts(before) == {
            (n, e): calls if e == "offered" else kept
            for n, e in before}
        return got

    paddle.set_flags({"pallas_interpret": kernels,
                      "pallas_attention_min_seq": 0 if kernels else 1024})
    try:
        (loss_a, buf_a), grads_a = loss_and_state(plain)
        (loss_b, buf_b), grads_b = loss_and_state(remat)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for name in grads_a:
        scale = float(jnp.abs(grads_a[name]).max())
        assert float(jnp.abs(grads_a[name] - grads_b[name]).max()) <= (
            1e-5 * scale), name
    # the buffers a recomputed block rewrites leave it all the same
    for name in buf_a:
        np.testing.assert_array_equal(np.asarray(buf_a[name]),
                                      np.asarray(buf_b[name]))
    assert any(not np.array_equal(np.asarray(buf_a[n]),
                                  np.asarray(buffers[n])) for n in buf_a)


def test_recompute_is_in_the_traced_program():
    net = build(use_recompute=True)
    params = net.functional_state()[0]
    ids = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p: framework_terms(net, p, ids)[1])).lower(params).as_text(
            debug_info=True)
    # the second forward of each block carries jax's scope for it, which
    # the benchmark's recompute_ms_per_step reads in a trace
    assert "rematted_computation" in text
    for scope in ("mla.q", "mla.kv", "mla.rope", "mla.core", "mla.out",
                  "moe.shared", "MultiTokenPredictor"):
        assert scope in text, scope


def test_all_experts_held_softmax_router_gives_pr25s_numbers_bit_for_bit():
    """The OLMoE arithmetic through the layer as it is now: the numbers
    the parent commit (PR 28, the layer of PR 25) gives for the same seed,
    to the last bit; and the held path with EVERY expert held takes the
    same rows in the same order, so it gives them too."""
    def run(held):
        paddle.seed(29)
        layer = moe.MoELayer(64, 32, 8, top_k=2, activation="swiglu",
                             gate_bias=False, norm_topk_prob=False,
                             aux_weight=0.01, z_loss_weight=0.001, held=held)
        x = paddle.to_tensor(np.random.default_rng(29).standard_normal(
            (2, 16, 64)).astype("float32"))
        return layer, np.asarray(layer(x)._value)

    layer, out = run(None)
    assert layer.resolved_mode() == "sorted"
    assert [float(v) for v in out[0, 0, :4]] == [
        0.016377253457903862, -0.023591678589582443, 0.10891302675008774,
        0.024724120274186134]
    assert float(out.sum()) == 0.3290029764175415
    assert float(np.abs(out).max()) == 0.2535169720649719
    assert float(layer.aux_loss) == 0.02813820168375969
    held_layer, held_out = run((0, 8))
    assert held_layer.resolved_mode() == "sorted_held"
    np.testing.assert_array_equal(held_out, out)


def test_dispatch_counter_has_the_held_path(model, ids):
    before = moe._DISPATCH_TOTAL.value(path="sorted_held")
    other = {p: moe._DISPATCH_TOTAL.value(path=p)
             for p in ("sorted", "dense", "capacity")}
    params = model.functional_state()[0]
    jax.make_jaxpr(lambda p: framework_terms(model, p, ids)[1])(params)
    # two expert layers and the MTP module's block, one call each
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") - before == 3
    assert all(moe._DISPATCH_TOTAL.value(path=p) == n
               for p, n in other.items())
