"""Trinity-Mini (HF ``afmoe``) on the CPU at a small size (hidden 64, 4 query
heads on 2 key/value heads of 16, a sliding window of 8 keys, a dense SwiGLU
of 96 in the first layer, 32 experts top-4 of width 32 with 8 held and a
shared expert, the five layers of the cell — sliding + dense, sliding x 3,
full without positions — seq 40, seeded random weights): the framework model
against the plain reference (benchmark/references/trinity-mini.py: every key
under an explicit mask, nothing imported from paddle_tpu) in float32 and
under amp O1, forward, loss and gradients, with and without recomputation;
each attention type alone on XLA's route and on the banded kernel; RoPE on
sliding layers only; the share test of the model-configs guide; the bias
update; the scopes and counters a traced step carries and the kernel
residuals its blocks keep; the parameters outside weight decay. The same
comparison runs at published widths on the chip
(benchmark/configs/trinity-mini.py check_train)."""
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
from paddle_tpu.ops import attention
from paddle_tpu.text.models import (AfmoeAttention, AfmoeDecoderLayer,
                                    AfmoeModel, LlamaMLP,
                                    ZeroCenteredRMSNorm, afmoe_layer_types,
                                    mtp_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = ["sliding_attention"] * 4 + ["full_attention"]
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_experts_per_tok": 4, "num_shared_experts": 1,
         "num_dense_layers": 1, "sliding_window": 8, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-5, "route_norm": True, "route_scale": 2.826,
         "load_balance_coeff": 0.001, "mup_enabled": True,
         "initializer_range": 0.1, "held_rows_factor": 8.0,
         # what the reference reads beside them
         "layer_types": TYPES, "run_layers": [0, 1, 2, 3, 4],
         "router_experts": 32, "n_routed_experts": 8, "held_experts": [8, 8]}
ROWS, SEQ = 2, 40      # five windows of 8

# Both sides compute the same equations in float32 on the CPU, in another
# summation order. bf16 arithmetic is off by 1e-3 and more; a window off by
# one key, a rotated full layer, a missing post-norm, an embedding without
# its sqrt(hidden) or a query head on the wrong key/value head by O(1).
RTOL = 2e-5
# gradients sum 80 tokens' contributions through five blocks; compared
# against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4
# amp O1: bf16 operands through five blocks, a share of the largest logit
AMP_RTOL = 3e-2


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references", "trinity-mini.py")
    spec = importlib.util.spec_from_file_location("trinity_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_kwargs(**over):
    skip = ("router_experts", "held_experts", "n_routed_experts",
            "run_layers")
    kw = {k: v for k, v in SIZES.items() if k not in skip}
    kw.update(num_experts=SIZES["router_experts"],
              held_experts=tuple(SIZES["held_experts"]))
    kw.update(over)
    return kw


def build(seed=40, **over):
    paddle.seed(seed)
    net = AfmoeModel(**model_kwargs(**over))
    rng = np.random.default_rng(seed)
    for _, sub in net.named_sublayers():
        if isinstance(sub, ZeroCenteredRMSNorm):
            # weights that are not at their start, so that a norm that is
            # left out, or applied on the wrong side of a sublayer, shows
            sub.weight.set_value(np.asarray(sub.weight._value) + rng.normal(
                0, 0.1, sub.weight.shape).astype(np.float32))
        if isinstance(sub, moe.MoELayer):
            # a selection bias that moves the choice and no weight
            sub.e_score_correction_bias.set_value(rng.normal(
                0, 0.05, sub.num_experts).astype(np.float32))
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
    auxiliary losses (none here) through the collector."""
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


def test_layers_go_by_their_types(model):
    assert model.layer_types == TYPES
    assert [layer.self_attn.window for layer in model.layers] == [
        8, 8, 8, 8, None]
    assert [layer.self_attn.scope for layer in model.layers] == [
        "swa"] * 4 + ["gattn"]
    # the first layer's MLP is dense, the others' the expert layer with its
    # shared expert, the sigmoid router's bias and no balance loss
    assert isinstance(model.layers[0].mlp, LlamaMLP)
    for layer in model.layers[1:]:
        assert isinstance(layer.mlp, moe.MoELayer)
        assert layer.mlp.scoring == "sigmoid" and layer.mlp.shared
        assert layer.mlp.aux_weight == 0.0
        assert layer.mlp.bias_update_speed == 0.001
        assert layer.mlp.routed_scale == 2.826
    # four norms a block, and the embedding's muP scale
    assert all(isinstance(getattr(layer, name), ZeroCenteredRMSNorm)
               and not getattr(layer, name).zero_centered
               for layer in model.layers
               for name in ("input_layernorm", "post_attention_layernorm",
                            "pre_mlp_layernorm", "post_mlp_layernorm"))
    assert model.embed_scale == 8.0
    published = afmoe_layer_types(32, 4)
    assert published.count("sliding_attention") == 24
    assert [i for i, t in enumerate(published)
            if t == "full_attention"] == list(range(3, 32, 4))
    # the cell's five layers: published layer 0, then one whole period
    assert [published[i] for i in (0, 4, 5, 6, 7)] == TYPES
    assert AfmoeModel(**model_kwargs(layer_types=None)).layer_types == (
        afmoe_layer_types(5, 4))
    with pytest.raises(ValueError, match="layer types"):
        AfmoeModel(**model_kwargs(layer_types=TYPES[:3]))
    with pytest.raises(ValueError, match="layer_type"):
        AfmoeAttention(64, "linear_attention")


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
    assert float(total) == float(ce)     # the bias balances: no loss term
    # the reference at another window, with a rotated full layer or without
    # the embedding's scale is another model: the comparison would see it
    for wrong in (dict(sliding_window=7), dict(mup_enabled=False),
                  dict(layer_types=["sliding_attention"] * 5)):
        off = reference.forward(weights(model), ids, dict(SIZES, **wrong))
        assert float(jnp.abs(off - ref[0]).max()) > 100 * RTOL * scale, wrong


def test_amp_o1_stays_near_the_float32_reference(model, reference, ids):
    """bf16 operands, float32 router, norms, RoPE and loss: the median token
    off by bf16's rounding and no more; and it really is bf16 (further than
    the float32 comparison allows)."""
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
    assert {"q_proj.weight", "k_proj.weight", "v_proj.weight",
            "self_attn.gate_proj.weight", "o_proj.weight", "q_norm.weight",
            "k_norm.weight", "input_layernorm.weight",
            "post_attention_layernorm.weight", "pre_mlp_layernorm.weight",
            "post_mlp_layernorm.weight", "mlp.w_gate", "gate.weight",
            "shared.up_proj.weight", "mlp.down_proj.weight",
            "embed_tokens.weight"} <= kinds | {
                ".".join(name.rsplit(".", 3)[-3:]) for name in got}
    for name in got:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        err = float(jnp.abs(got[name] - want[name]).max())
        assert err <= GRAD_RTOL * scale, (name, err, scale)


def _mixer_input(seed, seq=SEQ):
    return np.random.default_rng(seed).standard_normal(
        (ROWS, seq, 64)).astype(np.float32)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("layer_type", ["sliding_attention",
                                        "full_attention"])
def test_attention_layer_matches_the_reference(reference, layer_type,
                                               kernel):
    """Either attention type alone, on XLA's route and on the streaming
    kernel (in the Pallas interpreter; a sliding layer's calls are the
    banded ones: a window of 40 keys over 64-wide blocks): a head's own
    width, the QK-norms a head, RoPE where the layer has positions, the
    window, query head h on key/value head h // 2, the gate's own matrix."""
    paddle.seed(4)
    window = 40 if kernel else 8
    layer = AfmoeAttention(64, layer_type, num_heads=4, num_kv_heads=2,
                           head_dim=16, sliding_window=window)
    rng = np.random.default_rng(4)
    for norm in (layer.q_norm, layer.k_norm):
        norm.weight.set_value(1 + rng.normal(0, 0.2, 16).astype(np.float32))
    seq = 256 if kernel else SEQ
    x = _mixer_input(4, seq)
    dispatch.evict_ops("flash_attention")
    paddle.set_flags({"pallas_interpret": kernel,
                      "pallas_attention_min_seq": 0 if kernel else 1024})
    windowed = attention._WINDOW_ROUTE_TOTAL.value(
        route="stream" if kernel else "xla")
    try:
        got = np.asarray(layer(paddle.to_tensor(x))._value)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert attention._WINDOW_ROUTE_TOTAL.value(
        route="stream" if kernel else "xla") - windowed == (
            layer_type == "sliding_attention")
    w = {n: jnp.asarray(v) for n, v in layer.functional_state()[0].items()}
    sizes = dict(SIZES, sliding_window=window, reference_q_block=64)
    want = np.asarray(reference.attention(w, jnp.asarray(x), sizes, "",
                                          layer_type))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    # the other type's mathematics is another function of the same weights
    other = ("full_attention" if layer_type == "sliding_attention"
             else "sliding_attention")
    off = np.asarray(reference.attention(w, jnp.asarray(x), sizes, "",
                                         other))
    assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()


def test_rope_on_sliding_layers_only():
    """A full layer has no positions: ``_afmoe_heads`` rotates q and k of a
    sliding layer (position 0 alone stays where it was) and hands back a
    full layer's as the QK-norm left them; v is never rotated."""
    from paddle_tpu.text import models

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 6, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 6, 32)), jnp.float32)
    ones = jnp.ones((16,), jnp.float32)
    kw = dict(heads=4, kv_heads=2, d=16, eps=1e-5, base=10000.0)
    plain = models._afmoe_heads(q, k, k, ones, ones, rope=False, **kw)
    turned = models._afmoe_heads(q, k, k, ones, ones, rope=True, **kw)
    normed = models._rms_norm_f32(q.reshape(1, 6, 4, 16), ones, eps=1e-5,
                                  zero_centered=False).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(plain[0], normed, rtol=1e-6)
    np.testing.assert_allclose(
        turned[0], models._rope(normed, 10000.0, pairing="half"), rtol=1e-6)
    for a, b in zip(plain[:2], turned[:2]):
        np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])
        assert float(jnp.abs(a[:, :, 1:] - b[:, :, 1:]).max()) > 0.1
    np.testing.assert_array_equal(plain[2], turned[2])     # v: never
    # rotations keep a head's norm: sqrt(d) after the RMSNorm
    np.testing.assert_allclose(jnp.linalg.norm(turned[0], axis=-1), 4.0,
                               rtol=1e-4)


def _expert_layer(held, seed=11):
    paddle.seed(seed)
    layer = moe.MoELayer(
        64, 32, 32, top_k=4, activation="swiglu", gate_bias=False,
        norm_topk_prob=True, scoring="sigmoid", select_bias=True,
        bias_update_speed=0.001, routed_scale=2.826, shared_width=32,
        aux_weight=0.0, held=held, held_rows_factor=16.0)
    layer.e_score_correction_bias.set_value(np.random.default_rng(
        seed).normal(0, 0.05, 32).astype(np.float32))
    layer.eval()
    return layer


def test_share_test_eight_shares_and_the_shared_expert_once(reference):
    """The guide's share test at this model's layer (sigmoid scores, the
    choice by score + bias, top-k renormalised times route_scale, a shared
    expert): the routed parts that the EIGHT shares of 4 of 32 experts give,
    plus the shared expert counted ONCE, add up to what the uncut reference
    gives for the whole layer (at published sizes: 8 shares of 16 of
    128)."""
    whole = _expert_layer(None)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 16, 64)).astype(np.float32)
    state = whole.functional_state()
    w = {k: jnp.asarray(v) for tree in state for k, v in tree.items()}
    sizes = dict(SIZES, held_experts=[0, 32], n_routed_experts=32,
                 held_rows_factor=16.0)
    want, _, dropped, landed, load = reference.experts(
        w, jnp.asarray(x).reshape(32, 64), sizes, "")
    assert int(dropped) == 0 and int(landed) == 32 * 4 == int(load.sum())
    want = np.asarray(want).reshape(2, 16, 64)
    xt = paddle.to_tensor(x)
    shared = np.asarray(whole.shared(xt)._value)
    assert np.abs(shared).max() > 0
    total = np.zeros_like(x)
    for first in range(0, 32, 4):
        part = _expert_layer((first, 4))
        part.load_functional_state(
            {n: (v[first:first + 4] if n.startswith("w_") else v)
             for n, v in state[0].items()},
            {"e_score_correction_bias": state[1]["e_score_correction_bias"],
             "held_overflow": jnp.zeros((), jnp.int32)})
        assert part.resolved_mode() == "sorted_held"
        out = np.asarray(part(xt)._value)
        total += out - shared            # this share's routed part
        # the reference, given the same share, gives the same part
        ref_part = reference.experts(
            {**w, **{n: v[first:first + 4] for n, v in w.items()
                     if n.startswith("w_")}},
            jnp.asarray(x).reshape(32, 64),
            dict(sizes, held_experts=[first, 4]), "")[0]
        assert np.abs(out - np.asarray(ref_part).reshape(out.shape)).max() <= (
            RTOL * np.abs(want).max())
    total += shared                      # what every chip computes alike
    assert np.abs(total - want).max() <= RTOL * np.abs(want).max()


def test_the_bias_update_moves_the_bias_and_no_weight(reference, ids):
    """A traced training forward leaves every expert layer's selection bias
    moved by ``load_balance_coeff`` x sign(mean load - load) — the
    reference's rule on the reference's loads — and nothing else; the bias
    gets no gradient, and an eval forward moves nothing."""
    net = build()
    params, buffers = net.functional_state()
    after = jax.jit(lambda p, b: framework_terms(net, p, ids, b)[3])(
        params, buffers)
    w = weights(net)
    h = w["embed_tokens.weight"][ids] * 8.0
    moved = 0
    for i, layer_type in enumerate(TYPES):
        prefix = f"layers.{i}."
        if i >= 1:
            a = reference.attention(
                w, reference._rms(h, w[prefix + "input_layernorm.weight"],
                                  1e-5), SIZES, prefix + "self_attn.",
                layer_type)
            mid = h + reference._rms(
                a, w[prefix + "post_attention_layernorm.weight"], 1e-5)
            m = reference._rms(mid, w[prefix + "pre_mlp_layernorm.weight"],
                               1e-5)
            load = reference.experts(w, m.reshape(-1, 64), SIZES,
                                     prefix + "mlp.")[4]
            name = prefix + "mlp.e_score_correction_bias"
            want = reference.bias_update(buffers[name], load, 0.001)
            np.testing.assert_allclose(after[name], want, atol=1e-7)
            assert float(jnp.abs(after[name] - buffers[name]).max()) == (
                pytest.approx(0.001, rel=1e-3))
            moved += 1
        h = reference.block(w, h, SIZES, prefix, i < 1, layer_type)[0]
    assert moved == 4
    assert all(int(v) == 0 for n, v in after.items()
               if n.endswith("held_overflow"))
    grads = jax.grad(lambda b: framework_terms(net, params, ids, b)[1],
                     allow_int=True)(buffers)
    assert all(float(jnp.abs(g).max()) == 0 for n, g in grads.items()
               if n.endswith("e_score_correction_bias"))
    net.eval()
    try:
        still = framework_terms(net, params, ids, buffers)[3]
    finally:
        net.train()
    for name in buffers:
        np.testing.assert_array_equal(np.asarray(still[name]),
                                      np.asarray(buffers[name]))


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


def test_a_traced_step_carries_the_scopes_and_keeps_the_kernels_residuals(
        residual_counts):
    """With the kernels on (here in the Pallas interpreter) a traced step of
    the five recomputed blocks holds four banded cores and one full-causal
    core, each offering its output and log-sum-exp and each block keeping
    them (so no kernel runs twice), four expert layers on the held path,
    every windowed call counted on the ``stream`` route, and the scopes
    that tell the two attention types and a block's four norms apart."""
    net = build(use_recompute=True, sliding_window=40)
    params = net.functional_state()[0]
    ids = jnp.asarray(np.random.default_rng(7).integers(
        0, SIZES["vocab_size"], (1, 256)), jnp.int32)
    dispatch.evict_ops("flash_attention")
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 0})
    try:
        before = residual_counts()
        held = moe._DISPATCH_TOTAL.value(path="sorted_held")
        windowed = attention._WINDOW_ROUTE_TOTAL.value(route="stream")
        stream = attention._ROUTE_TOTAL.value(route="stream")
        text = jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text(debug_info=True)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert residual_counts(before) == dict.fromkeys(before, 5)
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") - held == 4
    assert attention._WINDOW_ROUTE_TOTAL.value(
        route="stream") - windowed == 4
    assert attention._ROUTE_TOTAL.value(route="stream") - stream == 5
    assert "rematted_computation" in text
    for scope in ("swa.proj", "swa.qk", "swa.repeat", "swa.core", "swa.out",
                  "gattn.proj", "gattn.qk", "gattn.repeat", "gattn.core",
                  "gattn.out", "AfmoeAttention", "AfmoeDecoderLayer",
                  "input_layernorm", "post_attention_layernorm",
                  "pre_mlp_layernorm", "post_mlp_layernorm", "moe.shared"):
        assert scope in text, scope
    for name in ("flash_band_fwd", "flash_band_bwd_dkv_dq",
                 "flash_stream_fwd", "flash_stream_bwd_dkv_dq"):
        assert name in text, name


def test_a_train_step_decays_no_norm_weight_and_moves_the_bias(ids):
    """Through ``spmd.build_train_step`` with a learning rate that leaves
    only the decay to see: ``apply_decay_param_fun`` reaches the compiled
    step, so every norm's weight keeps its value where a projection's
    weight shrinks; the loss is finite, nothing overflowed, and the step
    hands back the moved selection biases."""
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
        apply_decay_param_fun=lambda n: not n.endswith("norm_weight"))
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step, init = spmd.build_train_step(
        wrapper, lambda out, y: mtp_lm_loss(out[0], [], out[1],
                                            y)[0]._value,
        opt, mesh=mesh, donate=False)
    params, opt_state = init()
    before = {n: np.asarray(v) for n, v in params.items()}
    biases = {n: np.asarray(v)
              for n, v in wrapper.functional_state()[1].items()}
    loss, params, opt_state = step(params, opt_state, ids, ids)
    assert np.isfinite(float(loss))
    spared = 0
    # epsilon 1e30 silences Adam's own move: what is left is lr x wd x p
    for name, was in before.items():
        now = np.asarray(params[name])
        if "norm" in name:
            np.testing.assert_array_equal(now, was)
            spared += 1
        else:
            np.testing.assert_allclose(now, was * (1 - 1e-2 * 0.5),
                                       rtol=1e-5, atol=1e-9)
    # 5 x (four block norms + two QK-norms) + the final norm
    assert spared == 5 * 6 + 1
    after = wrapper.functional_state()[1]
    assert all(int(after[n]) == 0 for n in after
               if n.endswith("held_overflow"))
    for name, was in biases.items():
        if name.endswith("e_score_correction_bias"):
            assert np.abs(np.asarray(after[name]) - was).max() == (
                pytest.approx(0.001, rel=1e-3))
