"""Qwen3-Next-80B-A3B on the CPU at a small size (hidden 64, Gated DeltaNet
with 2 key heads serving 4 value heads of 16, gated attention with 4 query
heads on 2 key/value heads of 32 and 8 rotated features, 32 experts top-4 of
width 32 with 8 held and a gated shared expert, the four layer types of the
cell — Gated DeltaNet x 3, gated attention — seq 40, seeded random weights):
the framework model against the plain reference
(benchmark/references/qwen3-next-80b-a3b.py: the delta rule token by token,
nothing imported from paddle_tpu) in float32 and under amp O1, each mixer
alone, the share test of the model-configs guide at the model's own sizes,
the scopes and counters a traced step carries, the parameters outside weight
decay, and recomputation. The same comparison runs at published widths on
the chip (benchmark/configs/qwen3-next-80b-a3b.py check_train)."""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.amp.auto_cast import auto_cast
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.incubate import moe
from paddle_tpu.nn.aux_loss import collect_aux_losses, total_aux_loss
from paddle_tpu.ops import linear_attention
from paddle_tpu.text.models import (GatedDeltaNet, GatedGQAttention,
                                    Qwen3NextModel, ZeroCenteredRMSNorm,
                                    _rope, mtp_lm_loss,
                                    qwen3_next_layer_types)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "partial_rotary_factor": 0.25, "rope_theta": 1e7,
         "full_attention_interval": 4, "linear_num_key_heads": 2,
         "linear_num_value_heads": 4, "linear_key_head_dim": 16,
         "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
         "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
         "n_routed_experts": 8, "router_experts": 32, "held_experts": [8, 8],
         "num_experts_per_tok": 4, "norm_topk_prob": True,
         "router_aux_loss_coef": 0.001, "rms_norm_eps": 1e-6,
         "gdn_chunk": 16, "initializer_range": 0.1, "held_rows_factor": 8.0}
ROWS, SEQ = 2, 40      # two and a half chunks of 16

# Both sides compute the same equations in float32 on the CPU: the chunked
# scan against the recurrence, and otherwise another summation order. bf16
# arithmetic is off by 1e-3 and more, a wrong chunk boundary, a dropped
# pair, a key head on the wrong value head or a rotated 9th feature by O(1).
RTOL = 2e-5
# gradients sum 80 tokens' contributions through four blocks; compared
# against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4
# amp O1: bf16 operands through four blocks, a share of the largest logit
AMP_RTOL = 3e-2


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "qwen3-next-80b-a3b.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_kwargs(**over):
    skip = ("router_experts", "held_experts", "n_routed_experts")
    kw = {k: v for k, v in SIZES.items() if k not in skip}
    kw.update(num_experts=SIZES["router_experts"],
              held_experts=tuple(SIZES["held_experts"]))
    kw.update(over)
    return kw


def build(seed=38, **over):
    paddle.seed(seed)
    net = Qwen3NextModel(**model_kwargs(**over))
    rng = np.random.default_rng(seed)
    for _, sub in net.named_sublayers():
        if isinstance(sub, ZeroCenteredRMSNorm):
            # weights that are not at their start, so that a norm which
            # multiplied by w where it should by 1 + w shows
            sub.weight.set_value(np.asarray(sub.weight._value) + rng.normal(
                0, 0.1, sub.weight.shape).astype(np.float32))
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


def framework_terms(net, params, ids, buffers=None, amp=False):
    """(logits, total loss, cross-entropy, buffers afterwards) as a train
    step computes them: the cross-entropy on the final hidden states, the
    balance loss through the collector."""
    with loaded(net, params, buffers), auto_cast(
            enable=amp, level="O1", dtype="bfloat16"):
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


def test_layer_types_go_by_the_interval(model):
    assert model.layer_types == ["linear_attention"] * 3 + ["full_attention"]
    kinds = [type(layer.linear_attn if t == "linear_attention"
                  else layer.self_attn)
             for t, layer in zip(model.layer_types, model.layers)]
    assert kinds == [GatedDeltaNet] * 3 + [GatedGQAttention]
    # every layer has the expert layer, with its gated shared expert
    assert all(isinstance(layer.mlp, moe.MoELayer)
               and layer.mlp.shared_gate is not None
               for layer in model.layers)
    published = qwen3_next_layer_types(48, 4)
    assert published.count("linear_attention") == 36
    assert [i for i, t in enumerate(published)
            if t == "full_attention"] == list(range(3, 48, 4))
    assert published[:4] == model.layer_types


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


def test_amp_o1_stays_near_the_float32_reference(model, reference, ids):
    """bf16 operands, float32 router, decay, state, norms and loss: the
    median token off by bf16's rounding and no more; and it really is bf16
    (further than the float32 comparison allows)."""
    params = model.functional_state()[0]
    logits, total, _, _ = jax.jit(lambda p, a: framework_terms(
        model, p, a, amp=True)[:3] + (None,))(params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    # a token's error; the median token, which no swapped expert moves (at
    # this size a bf16 router input swaps some token's 4th and 5th expert,
    # and every later token of its row reads that: the chip's check
    # compares block by block for that reason)
    errs = np.asarray(jnp.abs(logits.astype(jnp.float32) - ref[0]).max(
        axis=-1)) / float(jnp.abs(ref[0]).max())
    assert 10 * RTOL < float(np.median(errs)) <= AMP_RTOL
    assert abs(float(total) - float(ref[1])) <= 3e-3 * abs(float(ref[1]))


def test_gradients_of_every_parameter_match_the_reference(model, reference,
                                                          ids):
    params, buffers = model.functional_state()
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[1]))(params)
    want = jax.grad(lambda p: reference.loss_terms(
        {**p, **buffers}, ids, SIZES)[0])(params)
    assert set(got) == set(want)
    kinds = {".".join(name.rsplit(".", 2)[-2:]) for name in got}
    # every parameter kind of the new layers is among them
    assert {"linear_attn.A_log", "linear_attn.dt_bias", "conv1d.weight",
            "in_proj_qkvz.weight", "in_proj_ba.weight", "norm.weight",
            "out_proj.weight", "q_proj.weight", "q_norm.weight",
            "k_norm.weight", "shared_gate.weight", "mlp.w_gate",
            "input_layernorm.weight"} <= kinds
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        err = float(jnp.abs(got[name] - want[name]).max())
        assert err <= GRAD_RTOL * scale, (name, err, scale)


def _mixer_input(seed, seq=SEQ):
    return np.random.default_rng(seed).standard_normal(
        (ROWS, seq, 64)).astype(np.float32)


def test_gated_delta_net_layer_matches_the_reference(reference):
    """The mixer alone: the fused projections' layout a key head at a time,
    ONE convolution over q | k | v, each key head on its two value heads,
    the scalar decay, the output norm times silu(z)."""
    paddle.seed(3)
    layer = GatedDeltaNet(64, num_k_heads=2, num_v_heads=4, head_k_dim=16,
                          head_v_dim=16, chunk=16)
    x = _mixer_input(3)
    got = np.asarray(layer(paddle.to_tensor(x))._value)
    w = {n: jnp.asarray(v) for n, v in layer.functional_state()[0].items()}
    want = np.asarray(reference.delta_net(w, jnp.asarray(x), SIZES, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    # a key head that served the wrong value heads would show
    wrong = dict(SIZES, linear_num_key_heads=4)
    with pytest.raises(Exception):
        reference.delta_net(w, jnp.asarray(x), wrong, "")


@pytest.mark.parametrize("kernel", [False, True])
def test_gated_attention_layer_matches_the_reference(reference, kernel):
    """The mixer alone, on XLA's route and on the streaming kernel (in the
    Pallas interpreter): a head's own width, query | gate a head, the
    zero-centred QK-norms, 8 of 32 features rotated, query head h on
    key/value head h // 2, sigmoid(gate) on the core's output."""
    paddle.seed(4)
    layer = GatedGQAttention(64, num_heads=4, num_kv_heads=2, head_dim=32)
    rng = np.random.default_rng(4)
    for norm in (layer.q_norm, layer.k_norm):
        norm.weight.set_value(rng.normal(0, 0.2, 32).astype(np.float32))
    seq = 256 if kernel else SEQ
    x = _mixer_input(4, seq)
    paddle.set_flags({"pallas_interpret": kernel,
                      "pallas_attention_min_seq": 0 if kernel else 1024})
    try:
        got = np.asarray(layer(paddle.to_tensor(x))._value)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    w = {n: jnp.asarray(v) for n, v in layer.functional_state()[0].items()}
    want = np.asarray(reference.attention(w, jnp.asarray(x), SIZES, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()


def test_rope_takes_a_rotary_width():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 2, 6, 32)),
                    jnp.float32)
    part = _rope(x, 1e7, pairing="half", rotary_dim=8)
    np.testing.assert_array_equal(np.asarray(part[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(
        np.asarray(part[..., :8]),
        np.asarray(_rope(x[..., :8], 1e7, pairing="half")), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(_rope(x, 1e4, rotary_dim=32)), np.asarray(_rope(x, 1e4)))


def test_zero_centred_norm_is_one_plus_w_in_float32():
    norm = ZeroCenteredRMSNorm(8, eps=1e-6)
    assert np.all(np.asarray(norm.weight._value) == 0)
    assert norm.weight.name.endswith("norm_weight")
    w = np.linspace(-0.5, 0.5, 8).astype(np.float32)
    norm.weight.set_value(w)
    x = np.random.default_rng(1).standard_normal((3, 8)).astype(np.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(np.asarray(norm(paddle.to_tensor(x))._value),
                               want, rtol=1e-5)
    out = norm(paddle.to_tensor(x.astype(jnp.bfloat16)))
    assert out.dtype == jnp.bfloat16      # float32 inside, x's dtype out
    plain = ZeroCenteredRMSNorm(8, zero_centered=False)
    assert np.all(np.asarray(plain.weight._value) == 1)


def _expert_layer(held, seed=11):
    paddle.seed(seed)
    layer = moe.MoELayer(
        64, 32, 32, top_k=4, activation="swiglu", gate_bias=False,
        norm_topk_prob=True, shared_width=32, shared_gate=True,
        aux_weight=0.0, held=held, held_rows_factor=16.0)
    layer.eval()
    return layer


def test_share_test_sixteen_shares_and_the_gated_shared_expert_once(
        reference):
    """The guide's share test at this model's layer (softmax router, top-k
    renormalised, a shared expert behind a gate of its own): the routed
    parts that the 16 shares of 2 of 32 experts give, plus the gated shared
    expert counted ONCE, add up to what the uncut reference gives for the
    whole layer (at published sizes: 16 shares of 32 of 512)."""
    whole = _expert_layer(None)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)
    state = whole.functional_state()
    w = {k: jnp.asarray(v) for tree in state for k, v in tree.items()}
    sizes = dict(SIZES, held_experts=[0, 32], n_routed_experts=32,
                 held_rows_factor=16.0)
    want, _, _, dropped, landed = reference.experts(
        w, jnp.asarray(x).reshape(32, 64), sizes, "")
    assert int(dropped) == 0 and int(landed) == 32 * 4
    want = np.asarray(want).reshape(2, 16, 64)
    xt = paddle.to_tensor(x)
    shared = np.asarray((paddle.nn.functional.sigmoid(whole.shared_gate(xt))
                         * whole.shared(xt))._value)
    assert np.abs(shared).max() > 0
    total = np.zeros_like(x)
    for first in range(0, 32, 2):
        part = _expert_layer((first, 2))
        part.load_functional_state(
            {n: (v[first:first + 2] if n.startswith("w_") else v)
             for n, v in state[0].items()}, {})
        assert part.resolved_mode() == "sorted_held"
        out = np.asarray(part(xt)._value)
        total += out - shared            # this share's routed part
        # the reference, given the same share, gives the same part
        ref_part = reference.experts(
            {**w, **{n: v[first:first + 2] for n, v in w.items()
                     if n.startswith("w_")}},
            jnp.asarray(x).reshape(32, 64),
            dict(sizes, held_experts=[first, 2]), "")[0]
        assert np.abs(out - np.asarray(ref_part).reshape(out.shape)).max() <= (
            RTOL * np.abs(want).max())
    total += shared                      # what every chip computes alike
    assert np.abs(total - want).max() <= RTOL * np.abs(want).max()


@pytest.mark.parametrize("given, conv, head_dim, seq, interpreter", [
    ("chunked_scalar", "xla", 16, SEQ, False),   # this file's widths
    ("kernel_scalar", "xla", 128, 72, True),     # a lane group a head, over
                                                 # a chunk, under one block
                                                 # of the convolution stage's
    ("kernel_scalar", "kernel", 128, 264, True),
])
def test_a_traced_step_carries_the_scopes_and_counts_the_paths(
        given, conv, head_dim, seq, interpreter):
    """Three Gated DeltaNet layers on the path the route gives the platform
    and the widths — the Mosaic kernels (here in the Pallas interpreter) at
    the published 128 a head, the XLA scan at this file's 16 —, always with
    the decay a head, one gated-attention layer, four expert layers on the
    held path."""
    from paddle_tpu.ops import attention

    paddle.set_flags({"pallas_interpret": interpreter})
    try:
        net = build(use_recompute=True, linear_key_head_dim=head_dim,
                    linear_value_head_dim=head_dim,
                    linear_num_key_heads=1 if head_dim == 128 else 2,
                    linear_num_value_heads=2 if head_dim == 128 else 4)
        params = net.functional_state()[0]
        ids = jnp.asarray(np.random.default_rng(7).integers(
            0, SIZES["vocab_size"], (ROWS, seq)), jnp.int32)
        paths = [p + s for p in ("kernel", "chunked", "recurrent")
                 for s in ("", "_scalar")]
        counts = {p: linear_attention._CORE_TOTAL.value(path=p)
                  for p in paths}
        entries = {e: linear_attention._ENTRY_TOTAL.value(path=given, entry=e)
                   for e in ("streams", "heads")}
        counts["held"] = moe._DISPATCH_TOTAL.value(path="sorted_held")
        counts["xla"] = attention._ROUTE_TOTAL.value(route="xla")
        routers = {p: moe._ROUTE_TOTAL.value(path=p)
                   for p in ("kernel", "xla")}
        stages = {p: linear_attention._CONV_TOTAL.value(path=p)
                  for p in ("kernel", "xla")}
        text = jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text(debug_info=True)
    finally:
        paddle.set_flags({"pallas_interpret": False})
    assert "rematted_computation" in text
    for scope in ("gdn.proj", "gdn.conv", "gdn.gate", "gdn.repeat",
                  "gdn.core", "gdn.out", "GatedDeltaNet", "gqa.proj",
                  "gqa.repeat", "gqa.core", "gqa.out", "GatedGQAttention",
                  "moe.shared"):
        assert scope in text, scope
    for p in paths:
        assert linear_attention._CORE_TOTAL.value(path=p) - counts[p] == (
            3 if p == given else 0), p
    # every layer hands the scan [B, T, H d] streams: none pays a relayout
    for e, n in entries.items():
        assert linear_attention._ENTRY_TOTAL.value(
            path=given, entry=e) - n == (3 if e == "streams" else 0), e
    # the convolution stage: its kernels at lane-wide heads on a row of at
    # least one of their blocks, the XLA stage at this file's widths
    for p, n in stages.items():
        assert linear_attention._CONV_TOTAL.value(path=p) - n == (
            3 if p == conv else 0), p
    assert ("conv_streams_fwd" in text and "conv_streams_bwd" in text) == (
        conv == "kernel")
    assert moe._DISPATCH_TOTAL.value(
        path="sorted_held") - counts["held"] == 4
    # the routers' choice: this file's 32 experts sort in XLA on every row
    # (the cell's 512 take the stage kernel: tests/test_moe_route_kernel.py)
    for p, n in routers.items():
        assert moe._ROUTE_TOTAL.value(path=p) - n == (
            4 if p == "xla" else 0), p
    assert attention._ROUTE_TOTAL.value(route="xla") - counts["xla"] == 1
    if given == "kernel_scalar":
        assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text


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


def test_a_traced_step_keeps_the_attention_layers_kernel_residuals(
        ids, residual_counts):
    """With the streaming kernel on the gated-attention core (here in the
    Pallas interpreter) a traced step counts ONE kernel call offering its
    output and log-sum-exp and one recomputed block keeping them
    (ops/residuals.py): the three DeltaNet blocks hold no offering
    kernel."""
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
    assert residual_counts(before) == dict.fromkeys(before, 1)


def test_a_train_step_decays_no_decay_scale_and_no_norm_weight(ids):
    """Through ``spmd.build_train_step`` with a learning rate that leaves
    only the decay to see: ``apply_decay_param_fun`` reaches the compiled
    step, so A_log, dt_bias and every norm's weight keep their values where
    a projection's weight shrinks; the loss is finite and nothing
    overflowed."""
    net = build(use_recompute=True)

    class Wrapper(paddle.nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            return self.lm.features(x), self.lm.lm_head.weight

    wrapper = Wrapper(net)
    wrapper.train()
    kept = ("A_log", "dt_bias", "norm_weight")
    opt = optimizer.AdamW(
        1e-2, parameters=net.parameters(), weight_decay=0.5, epsilon=1e30,
        apply_decay_param_fun=lambda n: not n.endswith(kept))
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step, init = spmd.build_train_step(
        wrapper, lambda out, y: mtp_lm_loss(out[0], [], out[1],
                                            y)[0]._value,
        opt, mesh=mesh, donate=False)
    params, opt_state = init()
    before = {n: np.asarray(v) for n, v in params.items()}
    loss, params, opt_state = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))
    spared = 0
    # epsilon 1e30 silences Adam's own move: what is left is lr x wd x p
    for name, was in before.items():
        now = np.asarray(params[name])
        if (name.endswith(("A_log", "dt_bias")) or "norm" in name):
            np.testing.assert_array_equal(now, was)
            spared += 1
        else:
            np.testing.assert_allclose(now, was * (1 - 1e-2 * 0.5),
                                       rtol=1e-5, atol=1e-9)
    # 3 x (A_log, dt_bias, the output norm) + 2 QK-norms + 8 block norms +
    # the final norm
    assert spared == 9 + 2 + 8 + 1
    after = wrapper.functional_state()[1]
    assert all(int(after[n]) == 0 for n in after
               if n.endswith("held_overflow"))


# ------------------------------------------------ one tiling: the streams
# Gated DeltaNet's stages stay on [B, T, H d] as KimiDeltaAttention's do
# (tests/test_kimi_linear_model.py holds the helper's sum and sigmoid-gated
# norm): here this layer's forms — the L2 norm over 16 KEY heads' features,
# the silu-gated norm, a key head's lanes laid down once a value head — each
# against its [.., H, d] view in float32, then the whole layer against
# PR 38's formulation, kept below.
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
    """(on streams, on the head view): x, gate [.., H d], w [d] -> an
    array."""
    from paddle_tpu.text import models

    def view(x):
        return x.reshape(*x.shape[:-1], heads, d)

    eps = 1e-6
    return {
        "l2": (lambda x, gate, w: linear_attention.l2_normed(x, heads, eps=eps,
                                                    scale=1.0),
               lambda x, gate, w: (view(x) * jax.lax.rsqrt(
                   jnp.sum(view(x) ** 2, -1, keepdims=True) + eps)).reshape(
                       x.shape)),
        "silu_gated_rms": (
            lambda x, gate, w: models._gdn_gated_norm(x, gate, w,
                                                      heads=heads, eps=eps),
            lambda x, gate, w: (view(x) * jax.lax.rsqrt(
                jnp.mean(view(x) ** 2, -1, keepdims=True) + eps)
                * w).reshape(x.shape) * jax.nn.silu(gate)),
        "repeat": (
            lambda x, gate, w: models._repeat_head_lanes(x, heads=heads,
                                                         repeats=3),
            lambda x, gate, w: jnp.repeat(view(x), 3, axis=-2).reshape(
                *x.shape[:-1], -1)),
    }[what]


@pytest.mark.parametrize("ambient", ["default", "highest"])
@pytest.mark.parametrize("what", ["l2", "silu_gated_rms", "repeat"])
def test_per_head_stages_on_streams_are_the_head_views(what, ambient):
    """Values and gradients to 1e-6 (the repeat: exactly), and the products
    state HIGHEST on the data whatever the ambient matmul precision."""
    heads, d = 3, 32
    ks = jax.random.split(jax.random.PRNGKey(38), 3)
    x = jax.random.normal(ks[0], (2, 24, heads * d)) * jnp.repeat(
        jnp.asarray([1e-2, 1.0, 30.0]), d)
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
    if what == "repeat":
        assert not precisions             # slices side by side: no product
    else:
        assert precisions and all(
            p is not None and p[0] == jax.lax.Precision.HIGHEST
            for p in precisions), precisions
    for a, b in zip((got,) + got_grads, (want,) + want_grads):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape
        if a.ndim == 3:       # a head is not judged by a larger head's size
            a, b = (t.reshape(-1, t.shape[-1] // d, d) for t in (a, b))
            scale = np.abs(b).max(axis=(0, 2), keepdims=True)
        else:
            scale = np.abs(b).max()
        assert (np.abs(a - b) <= (0.0 if what == "repeat" else 1e-6)
                * scale + 1e-30).all()


def _gdn_layer_on_the_head_view(p, x, *, key_heads, value_heads, d_k, d_v,
                                chunk, l2_eps, norm_eps):
    """``GatedDeltaNet.forward`` as it stood in PR 38: q, k, v and the
    output norm on [B, T, H, d], q and k repeated on the head axis, the
    scan called with heads."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.text import models

    f32 = jnp.float32
    per_key = value_heads // key_heads
    mixed, z, b, a = models._gdn_split(
        x @ p["in_proj_qkvz.weight"], x @ p["in_proj_ba.weight"],
        key_heads=key_heads, d_k=d_k, d_v=d_v, per_key=per_key)
    conv = F._causal_depthwise_conv1d(mixed, p["conv1d.weight"],
                                      activation="silu")
    key, lead = key_heads * d_k, conv.shape[:-1]

    def l2(x, scale):
        xf = x.reshape(*lead, key_heads, d_k).astype(f32)
        return (xf * (jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                                    + l2_eps) * scale)).astype(x.dtype)

    q = jnp.repeat(l2(conv[..., :key], d_k ** -0.5), per_key, axis=2)
    k = jnp.repeat(l2(conv[..., key:2 * key], 1.0), per_key, axis=2)
    v = conv[..., 2 * key:].reshape(*lead, -1, d_v)
    g = models._gdn_decay(a, p["A_log"], p["dt_bias"])
    o = linear_attention._chunked_output(q, k, v, g, models._gdn_beta(b),
                                         chunk=chunk)
    of = o.astype(f32)
    normed = of * jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True)
                                + norm_eps) * p["norm.weight"].astype(f32)
    out = normed.reshape(z.shape) * jax.nn.silu(z.astype(f32))
    return out.astype(o.dtype) @ p["out_proj.weight"]


def test_the_layer_on_streams_is_the_layer_on_the_head_view():
    """Output and every parameter's gradient (and the input's) of
    ``GatedDeltaNet`` against the [.., H, d] formulation above."""
    paddle.seed(4)
    layer = GatedDeltaNet(64, num_k_heads=2, num_v_heads=4, head_k_dim=16,
                          head_v_dim=16, chunk=16)
    x = jnp.asarray(_mixer_input(4, seq=50))
    params = dict(layer.functional_state()[0])

    def on_streams(p, x):
        with loaded(layer, p):
            return layer(Tensor(x, stop_gradient=False))._value

    def on_the_head_view(p, x):
        return _gdn_layer_on_the_head_view(
            p, x, key_heads=2, value_heads=4, d_k=16, d_v=16, chunk=16,
            l2_eps=layer.l2_eps, norm_eps=layer.norm.eps)

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
