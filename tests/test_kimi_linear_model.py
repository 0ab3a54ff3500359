"""Kimi-Linear-48B-A3B on the CPU at a small size (hidden 64, four KDA heads
of 16, latent attention with 24-wide keys and 16-wide values, 32 experts
top-4 of width 32 with 8 held, the five layer types of the cell — KDA +
dense, KDA + experts x 2, latent attention + experts, KDA + experts — seq
40, seeded random weights): the framework model against the plain reference
(benchmark/references/kimi-linear-48b-a3b.py: the delta rule token by token,
nothing imported from paddle_tpu), the layer types by the config's 1-indexed
lists, latent attention without a q rank and without rotation, the share
test of the model-configs guide at the model's own sizes, the scopes and
counters a traced step carries, the decay parameters outside weight decay,
and recomputation. The same comparison runs at published widths on the chip
(benchmark/configs/kimi-linear-48b-a3b.py check_train)."""
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
from paddle_tpu.ops import linear_attention
from paddle_tpu.text.models import (KimiDeltaAttention, KimiLinearModel,
                                    MLAttention, kimi_layer_types,
                                    mtp_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
          "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4}
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
         "num_attention_heads": 4, "intermediate_size": 96,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "router_experts": 32, "held_experts": [8, 8],
         "num_experts_per_tok": 4, "n_shared_experts": 1,
         "first_k_dense_replace": 1, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "rms_norm_eps": 1e-5, "moe_renormalize": True,
         "routed_scaling_factor": 2.446, "linear_attn_config": LINEAR,
         "kda_gate_rank": 16, "kda_chunk": 16, "bias_update_speed": 0.001,
         "balance_loss_weight": 1.25e-5, "initializer_range": 0.1,
         "held_rows_factor": 8.0}
ROWS, SEQ = 2, 40      # two and a half chunks of 16

# Both sides compute the same equations in float32 on the CPU: the chunked
# scan against the recurrence, and otherwise another summation order. bf16
# arithmetic is off by 1e-3 and more, a wrong chunk boundary, a dropped
# pair or a rotated key by O(1).
RTOL = 2e-5
# gradients sum 80 tokens' contributions through five blocks; compared
# against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "kimi-linear-48b-a3b.py")
    spec = importlib.util.spec_from_file_location("kimi_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_kwargs(**over):
    skip = ("router_experts", "held_experts", "n_routed_experts",
            "moe_renormalize")
    kw = {k: v for k, v in SIZES.items() if k not in skip}
    kw.update(n_routed_experts=SIZES["router_experts"],
              held_experts=tuple(SIZES["held_experts"]),
              norm_topk_prob=SIZES["moe_renormalize"])
    kw.update(over)
    return kw


def build(seed=32, **over):
    paddle.seed(seed)
    net = KimiLinearModel(**model_kwargs(**over))
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
    """(logits, total loss, cross-entropy, buffers afterwards) as a train
    step computes them: the cross-entropy on the final hidden states, the
    balance loss through the collector."""
    with loaded(net, params, buffers):
        x = Tensor(ids, stop_gradient=True)
        with collect_aux_losses() as auxes:
            hidden = net.features(x)
        logits = net.lm_head(hidden)._value
        ce = mtp_lm_loss(hidden, [], net.lm_head.weight, x)[0]._value
        return (logits, ce + total_aux_loss(auxes), ce,
                net.functional_state()[1])


def weights(net):
    params, buffers = net.functional_state()
    return {**params, **buffers}


def test_layer_types_go_by_the_one_indexed_lists(model):
    assert model.layer_types == ["kda", "kda", "kda", "mla", "kda"]
    kinds = [type(layer.self_attn) for layer in model.layers]
    assert kinds == [KimiDeltaAttention] * 3 + [MLAttention,
                                                KimiDeltaAttention]
    # the leading layer is dense, every other one the expert layer
    assert [isinstance(layer.mlp, moe.MoELayer) for layer in model.layers] == [
        False, True, True, True, True]
    # the published lists: 20 KDA layers and 7 of latent attention, the
    # 27th among them
    published = kimi_layer_types(
        {"kda_layers": [i for i in range(1, 27) if i % 4],
         "full_attn_layers": [4, 8, 12, 16, 20, 24, 27]}, 27)
    assert published.count("kda") == 20 and published[26] == "mla"
    assert published[:5] == model.layer_types
    with pytest.raises(ValueError, match="exactly one"):
        kimi_layer_types({"kda_layers": [1], "full_attn_layers": [1, 2]}, 2)
    with pytest.raises(ValueError, match="exactly one"):
        kimi_layer_types({"kda_layers": [1], "full_attn_layers": [3]}, 3)


def test_logits_and_loss_match_the_reference(model, reference, ids):
    params = model.functional_state()[0]
    logits, total, ce, _ = jax.jit(
        lambda p, a: framework_terms(model, p, a)[:3] + (None,))(params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    scale = float(jnp.abs(ref[0]).max())
    assert float(jnp.abs(logits - ref[0]).max()) <= RTOL * scale
    assert float(ref[4]) == 0            # nothing dropped
    for got, want in ((total, ref[1]), (ce, ref[2])):
        assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    assert float(total) > float(ce)      # the balance term is in the total


def test_gradients_of_every_parameter_match_the_reference(model, reference,
                                                          ids):
    params, buffers = model.functional_state()
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[1]))(params)
    want = jax.grad(lambda p: reference.loss_terms(
        {**p, **buffers}, ids, SIZES)[0])(params)
    assert set(got) == set(want)
    kinds = {name.rsplit(".", 2)[-2] + "." + name.rsplit(".", 1)[-1]
             if name.count(".") > 1 else name for name in got}
    # every parameter kind of the new layer is among them
    assert {"self_attn.A_log", "self_attn.dt_bias", "q_conv.weight",
            "f_b_proj.weight", "b_proj.weight", "g_a_proj.weight",
            "o_norm.weight", "q_proj.weight"} <= kinds
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        err = float(jnp.abs(got[name] - want[name]).max())
        assert err <= GRAD_RTOL * scale, (name, err, scale)


@pytest.mark.parametrize("kernel", [False, True])
def test_latent_attention_without_q_rank_and_without_rotation(reference,
                                                              kernel):
    """``MLAttention(q_lora_rank=None, rope=False)`` alone against the
    reference's naive form (one q projection, the shared key part broadcast
    as it is); with ``pallas_interpret`` the core is the streaming kernel
    at 24-wide keys and 16-wide values."""
    paddle.seed(3)
    attn = MLAttention(64, 4, None, 32, 16, 8, 16, rms_norm_eps=1e-5,
                       rope=False)
    names = {n for n, _ in attn.named_parameters()}
    assert "q_proj.weight" in names
    assert not any(n.startswith("q_a_") or n.startswith("q_b_")
                   for n in names)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 64, 64)), jnp.float32)
    w = dict(attn.functional_state()[0])
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
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2e-5 * scale
    # rotating would show: the rotated layer is another function
    paddle.seed(3)
    rotated = MLAttention(64, 4, None, 32, 16, 8, 16, rms_norm_eps=1e-5)
    other = rotated(paddle.to_tensor(np.asarray(x)))._value
    assert float(jnp.abs(other - want).max()) > 1e-2 * scale


def test_delta_attention_layer_matches_the_reference(reference):
    """One ``KimiDeltaAttention`` alone, at a length that is no multiple of
    its chunk, against the reference's sublayer."""
    paddle.seed(5)
    layer = KimiDeltaAttention(64, num_heads=4, head_dim=16, gate_rank=16,
                               chunk=16)
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 50, 64)), jnp.float32)
    want = reference.delta_attention(dict(layer.functional_state()[0]), x,
                                     SIZES, "")
    got = layer(paddle.to_tensor(np.asarray(x)))._value
    assert float(jnp.abs(got - want).max()) <= RTOL * float(
        jnp.abs(want).max())
    # the decay starts where the assumed initialisers put it: A in (1, 16),
    # dt = softplus(dt_bias) in (1e-3, 1e-1)
    a = np.exp(np.asarray(layer.A_log._value))
    dt = np.log1p(np.exp(np.asarray(layer.dt_bias._value)))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


def _expert_layer(held, seed=11):
    paddle.seed(seed)
    layer = moe.MoELayer(
        64, 32, 32, top_k=4, activation="swiglu", gate_bias=False,
        norm_topk_prob=True, scoring="sigmoid", select_bias=True,
        routed_scale=2.446, shared_width=32, aux_weight=0.0, held=held,
        held_rows_factor=8.0)
    layer.eval()
    return layer


def test_share_test_the_shares_and_the_shared_expert_once(reference):
    """The guide's share test at this model's layer (sigmoid router x
    2.446, one shared expert): the routed parts that the 4 shares of 8 of
    32 experts give, plus the shared expert counted ONCE, add up to what
    the uncut reference gives for the whole layer (at published sizes: 32
    shares of 8 of 256)."""
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
    want = np.asarray(want).reshape(2, 16, 64)
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
        total += out - shared            # this share's routed part
        # the reference, given the same share, gives the same part
        ref_part = reference.experts(
            {**w, **{n: v[first:first + 8] for n, v in w.items()
                     if n.startswith("w_")}},
            jnp.asarray(x).reshape(32, 64),
            dict(SIZES, held_experts=[first, 8]), "")[0]
        assert np.abs(out - np.asarray(ref_part).reshape(out.shape)).max() <= (
            RTOL * np.abs(want).max())
    total += shared                      # what every chip computes alike
    assert np.abs(total - want).max() <= RTOL * np.abs(want).max()


@pytest.mark.parametrize("given, conv, head_dim, seq, interpreter", [
    ("chunked", "xla", 16, SEQ, False),   # this file's widths: XLA's paths
    ("kernel", "xla", 128, 72, True),     # a lane group a head, over one
                                          # chunk, under one block of the
                                          # convolution stage's
    ("kernel", "kernel", 128, 264, True),
])
def test_a_traced_step_carries_the_scopes_and_counts_the_paths(
        given, conv, head_dim, seq, interpreter):
    """Four KDA layers on the path the route gives the platform and the
    widths — the Mosaic kernels (here in the Pallas interpreter) at the
    published 128 a head, the XLA scan at this file's 16 — one latent-
    attention layer, four expert layers on the held path."""
    from paddle_tpu.ops import attention

    paddle.set_flags({"pallas_interpret": interpreter})
    try:
        net = build(use_recompute=True, linear_attn_config=dict(
            LINEAR, head_dim=head_dim, num_heads=64 // head_dim or 4))
        params = net.functional_state()[0]
        ids = jnp.asarray(np.random.default_rng(7).integers(
            0, SIZES["vocab_size"], (ROWS, seq)), jnp.int32)
        paths = ("kernel", "chunked", "recurrent")
        counts = {p: linear_attention._CORE_TOTAL.value(path=p)
                  for p in paths}
        entries = {e: linear_attention._ENTRY_TOTAL.value(path=given, entry=e)
                   for e in ("streams", "heads")}
        counts["held"] = moe._DISPATCH_TOTAL.value(path="sorted_held")
        counts["xla"] = attention._ROUTE_TOTAL.value(route="xla")
        stages = {p: linear_attention._CONV_TOTAL.value(path=p)
                  for p in ("kernel", "xla")}
        text = jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text(debug_info=True)
    finally:
        paddle.set_flags({"pallas_interpret": False})
    # the second forward of each block carries jax's scope for it, which
    # the benchmark's recompute_ms_per_step reads in a trace
    assert "rematted_computation" in text
    for scope in ("kda.proj", "kda.conv", "kda.gate", "kda.core", "kda.out",
                  "KimiDeltaAttention", "mla.q", "mla.core", "moe.shared"):
        assert scope in text, scope
    # one count a traced call (jax traces a recomputed block once and
    # replays its jaxpr in the backward pass)
    for p in paths:
        assert linear_attention._CORE_TOTAL.value(path=p) - counts[p] == (
            4 if p == given else 0), p
    # every layer hands the scan [B, T, H d] streams: none pays a relayout
    for e, n in entries.items():
        assert linear_attention._ENTRY_TOTAL.value(
            path=given, entry=e) - n == (4 if e == "streams" else 0), e
    # the convolution stage: its kernels at lane-wide heads on a row of at
    # least one of their blocks, the XLA stage at this file's widths
    for p, n in stages.items():
        assert linear_attention._CONV_TOTAL.value(path=p) - n == (
            4 if p == conv else 0), p
    assert ("conv_streams_fwd" in text and "conv_streams_bwd" in text) == (
        conv == "kernel")
    assert moe._DISPATCH_TOTAL.value(
        path="sorted_held") - counts["held"] == 4
    assert attention._ROUTE_TOTAL.value(route="xla") - counts["xla"] == 1


def test_recomputation_gives_the_same_loss_and_gradients(ids):
    plain, remat = build(use_recompute=False), build(use_recompute=True)
    params, buffers = plain.functional_state()

    def loss_and_state(net):
        def fn(p):
            out = framework_terms(net, p, ids, buffers)
            return out[1], out[3]
        return jax.jit(jax.value_and_grad(fn, has_aux=True))(params)

    (loss_a, buf_a), grads_a = loss_and_state(plain)
    (loss_b, buf_b), grads_b = loss_and_state(remat)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for name in grads_a:
        scale = float(jnp.abs(grads_a[name]).max())
        assert float(jnp.abs(grads_a[name] - grads_b[name]).max()) <= (
            1e-5 * scale), name
    for name in buf_a:
        np.testing.assert_array_equal(np.asarray(buf_a[name]),
                                      np.asarray(buf_b[name]))


def test_a_traced_step_keeps_the_one_latent_layers_kernel_residuals(
        ids, residual_counts):
    """With the streaming kernel on the latent-attention core (here in the
    Pallas interpreter) a traced step counts ONE kernel call offering its
    output and log-sum-exp and one recomputed block keeping them
    (ops/residuals.py): the four KDA blocks hold no offering kernel."""
    from paddle_tpu.ops import residuals

    net = build(use_recompute=True)
    params = net.functional_state()[0]
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 0})
    try:
        before = residual_counts()
        jax.make_jaxpr(jax.grad(
            lambda p: framework_terms(net, p, ids)[1]))(params)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert residual_counts(before) == (
        dict.fromkeys(before, 1))


def test_blocks_without_an_offering_kernel_are_checkpointed_as_before(
        ids, monkeypatch, residual_counts):
    """On the XLA routes no block of the model holds a kernel that offers
    residuals (the KDA blocks never do): ``recompute`` under its policy
    lowers the step's gradient to the text ``jax.checkpoint`` with no
    policy gives, and keeps nothing."""
    from paddle_tpu.ops import residuals

    net = build(use_recompute=True)
    params = net.functional_state()[0]

    def lowered():
        return jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text()

    before = residual_counts()
    now = lowered()
    assert residual_counts() == before
    monkeypatch.setattr(residuals, "keep_offered", None)
    assert lowered() == now and "optimization_barrier" in now


def test_a_train_step_decays_every_weight_but_the_decays_own(ids):
    """Through ``spmd.build_train_step`` with a learning rate that leaves
    only the decay to see: ``apply_decay_param_fun`` reaches the compiled
    step, so A_log and dt_bias keep their values where a projection's
    weight shrinks; the loss is finite and the buffers moved."""
    net = build(use_recompute=True)

    class Wrapper(paddle.nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            return self.lm.features(x), self.lm.lm_head.weight

    wrapper = Wrapper(net)
    wrapper.train()
    opt = optimizer.AdamW(
        1e-2, parameters=net.parameters(), weight_decay=0.5, epsilon=1e30,
        apply_decay_param_fun=lambda n: not n.endswith(("A_log", "dt_bias")))
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step, init = spmd.build_train_step(
        wrapper, lambda out, y: mtp_lm_loss(out[0], [], out[1],
                                            y)[0]._value,
        opt, mesh=mesh, donate=False)
    params, opt_state = init()
    before = {n: np.asarray(v) for n, v in params.items()}
    loss, params, opt_state = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))
    # epsilon 1e30 silences Adam's own move: what is left is lr x wd x p
    for name, was in before.items():
        now = np.asarray(params[name])
        if name.endswith(("A_log", "dt_bias")):
            np.testing.assert_array_equal(now, was)
        else:
            np.testing.assert_allclose(now, was * (1 - 1e-2 * 0.5),
                                       rtol=1e-5, atol=1e-9)
    after = wrapper.functional_state()[1]
    assert sum(n.endswith("e_score_correction_bias") for n in after) == 4
    assert all(int(after[n]) == 0 for n in after
               if n.endswith("held_overflow"))


# ------------------------------------------------ one tiling: the streams
# The layer's element-wise stages stay on [B, T, H d] (text/models.py): a
# head's float32 statistics come from products with a 0/1 matrix
# (ops.linear_attention.head_sums / head_rsqrt), not from a [.., H, d] view.
# Held here to that view, in float32: the helper alone, then the whole layer
# against PR 38's formulation, kept below.
def _dot_precisions(jaxpr):
    """The ``precision`` of every dot_general, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [
                    value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    found += _dot_precisions(inner)
    return found


def _per_head(what, heads, d):
    """(on streams, on the head view) of one statistic: x, gate [.., H d],
    w [d] -> an array."""
    from paddle_tpu.text import models

    def view(x):
        return x.reshape(*x.shape[:-1], heads, d)

    eps = 1e-6
    return {
        "sum": (lambda x, gate, w: linear_attention.head_sums(x, heads),
                lambda x, gate, w: view(x).sum(-1)),
        "l2": (lambda x, gate, w: linear_attention.l2_normed(x, heads, eps=eps,
                                                    scale=d ** -0.5),
               lambda x, gate, w: (view(x) * jax.lax.rsqrt(
                   jnp.sum(view(x) ** 2, -1, keepdims=True) + eps)
                   * d ** -0.5).reshape(x.shape)),
        "gated_rms": (lambda x, gate, w: models._gated_head_norm(
            x, gate, w, heads, eps=eps, activation=jax.nn.sigmoid),
                      lambda x, gate, w: (view(x) * jax.lax.rsqrt(
                          jnp.mean(view(x) ** 2, -1, keepdims=True) + eps)
                          * w).reshape(x.shape) * jax.nn.sigmoid(gate)),
    }[what]


@pytest.mark.parametrize("ambient", ["default", "highest"])
@pytest.mark.parametrize("what", ["sum", "l2", "gated_rms"])
def test_per_head_statistics_on_streams_are_the_head_views(what, ambient):
    """Values and gradients to 1e-6, and every product of the helper states
    its precision — HIGHEST on the data — so that it is float32 in earnest
    under ANY ambient ``jax.default_matmul_precision`` (on a TPU the default
    is one bf16 pass: another sum of squares)."""
    heads, d = 4, 32
    ks = jax.random.split(jax.random.PRNGKey(39), 3)
    # heads of very different sizes: a sum that leaked across would show
    x = jax.random.normal(ks[0], (2, 24, heads * d)) * jnp.repeat(
        jnp.asarray([1e-2, 1.0, 30.0, 3.0]), d)
    gate, w = jax.random.normal(ks[1], x.shape), 1.0 + 0.1 * jax.random.normal(
        ks[2], (d,))
    streams, head_view = _per_head(what, heads, d)

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out * jnp.cos(jnp.arange(
                out.size, dtype=jnp.float32).reshape(out.shape))), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    with jax.default_matmul_precision(ambient):
        (_, got), got_grads = both(streams)(x, gate, w)
        precisions = _dot_precisions(jax.make_jaxpr(
            jax.grad(lambda *a: jnp.sum(streams(*a)), argnums=0))(
                x, gate, w).jaxpr)
    (_, want), want_grads = both(head_view)(x, gate, w)
    highest = jax.lax.Precision.HIGHEST
    assert precisions and all(
        p is not None and p[0] == highest for p in precisions), precisions

    def near(a, b):
        # per head: the smallest head is not judged by the largest's size
        a, b = (np.asarray(t).reshape(-1, heads, t.shape[-1] // heads)
                if t.ndim == 3 else np.asarray(t)[None, None]
                for t in (a, b))
        assert a.shape == b.shape
        scale = np.abs(b).max(axis=(0, 2), keepdims=True)
        assert (np.abs(a - b) <= 1e-6 * scale + 1e-30).all()

    near(got, want)
    for a, b in zip(got_grads, want_grads):
        near(a, b)


def _kda_layer_on_the_head_view(p, x, *, heads, chunk, l2_eps, norm_eps):
    """``KimiDeltaAttention.forward`` as it stood until PR 38: q, k, v, the
    decay and the output norm on [B, T, H, d], the scan called with heads."""
    from paddle_tpu.nn import functional as F

    f32 = jnp.float32

    def conv(x, w):
        return F._causal_depthwise_conv1d(x, w, activation="silu")

    def split(x):
        return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)

    def l2(x, scale):
        xf = split(x).astype(f32)
        return (xf * (jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                                    + l2_eps) * scale)).astype(x.dtype)

    q, k, v = (x @ p[f"{n}_proj.weight"] for n in "qkv")
    d = q.shape[-1] // heads
    q = l2(conv(q, p["q_conv.weight"]), d ** -0.5)
    k = l2(conv(k, p["k_conv.weight"]), 1.0)
    v = split(conv(v, p["v_conv.weight"]))
    z = jnp.dot((x @ p["f_a_proj.weight"]).astype(f32),
                p["f_b_proj.weight"].astype(f32)) + p["dt_bias"].astype(f32)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(split(z))
    beta = jax.nn.sigmoid(jnp.dot(x.astype(f32),
                                  p["b_proj.weight"].astype(f32)))
    gate = (x @ p["g_a_proj.weight"]) @ p["g_b_proj.weight"]
    o = linear_attention._chunked_output(q, k, v, g, beta, chunk=chunk)
    of = o.astype(f32)
    normed = of * jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True)
                                + norm_eps) * p["o_norm.weight"].astype(f32)
    out = normed.reshape(gate.shape) * jax.nn.sigmoid(gate.astype(f32))
    return out.astype(o.dtype) @ p["o_proj.weight"]


def test_the_layer_on_streams_is_the_layer_on_the_head_view():
    """Output and every parameter's gradient (and the input's) of
    ``KimiDeltaAttention`` against the [.., H, d] formulation above."""
    paddle.seed(9)
    layer = KimiDeltaAttention(64, num_heads=4, head_dim=16, gate_rank=16,
                               chunk=16)
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, 50, 64)), jnp.float32)
    params = dict(layer.functional_state()[0])

    def on_streams(p, x):
        with loaded(layer, p):
            return layer(Tensor(x, stop_gradient=False))._value

    def on_the_head_view(p, x):
        return _kda_layer_on_the_head_view(
            p, x, heads=4, chunk=16, l2_eps=layer.l2_eps,
            norm_eps=layer.o_norm.eps)

    def both(fn):
        def loss(p, x):
            out = fn(p, x)
            return jnp.sum(out * jnp.cos(jnp.arange(
                out.size, dtype=jnp.float32).reshape(out.shape))), out
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    (_, got), (got_p, got_x) = both(on_streams)
    (_, want), (want_p, want_x) = both(on_the_head_view)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    assert set(got_p) == set(want_p) == set(params)
    for name in list(params) + ["x"]:
        a, b = (got_x, want_x) if name == "x" else (got_p[name], want_p[name])
        assert float(jnp.abs(b).max()) > 0, name
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(
            jnp.abs(b).max()), name
