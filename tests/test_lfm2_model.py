"""LFM2-8B-A1B (HF ``lfm2_moe``) on the CPU at a small size (hidden 64, 4
query heads on 2 key/value heads of 16, a 3-tap gated short convolution, a
dense SwiGLU of 96 in the first layer, 32 experts top-4 of width 32 with 8
held and no shared expert, a tied head, the five layers of the cell — conv +
dense, attention, conv x 3 — 2 rows of 40 tokens, seeded random weights):
the framework model against the plain reference
(benchmark/references/lfm2-8b-a1b.py: three shifted multiply-adds, every key
under an explicit mask, nothing imported from paddle_tpu) in float32 and
under amp O1, forward, loss and every parameter's gradient, with and without
recomputation; the short convolution's reach and its row boundary; the
attention module on both routes and the streaming kernel at heads of 64
under the interpreter; the share test of the model-configs guide; the bias
update; the counters and scopes a traced step carries; the parameters
outside weight decay. The same comparison runs at published widths on the
chip (benchmark/configs/lfm2-8b-a1b.py check_train)."""
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
from paddle_tpu.ops import attention, linear_attention
from paddle_tpu.ops.pallas import flash_attention
from paddle_tpu.text.models import (Lfm2Attention, Lfm2DecoderLayer,
                                    Lfm2Model, Lfm2ShortConv, LlamaMLP,
                                    ZeroCenteredRMSNorm, lfm2_layer_types,
                                    mtp_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_experts_per_tok": 4, "num_dense_layers": 1, "conv_L_cache": 3,
         "rope_theta": 1e6, "norm_eps": 1e-5, "norm_topk_prob": True,
         "use_expert_bias": True, "routed_scaling_factor": 1.0,
         "bias_update_speed": 0.001, "initializer_range": 0.1,
         "held_rows_factor": 4.0,
         # what the reference reads beside them
         "layer_types": TYPES, "run_layers": [0, 1, 2, 3, 4],
         "router_experts": 32, "n_routed_experts": 8, "held_experts": [8, 8]}
ROWS, SEQ = 2, 40

# Both sides compute the same equations in float32 on the CPU, in another
# summation order. bf16 arithmetic is off by 1e-3 and more; taps shifted by
# a token, swapped gates, a row that reads the row before it, a query head
# on the wrong key/value head or an untied head by O(1).
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
    path = os.path.join(ROOT, "benchmark", "references", "lfm2-8b-a1b.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
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


def build(seed=44, **over):
    paddle.seed(seed)
    net = Lfm2Model(**model_kwargs(**over))
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
    step computes them: the cross-entropy on the final hidden states and
    the TIED head's weight, the auxiliary losses (none here) through the
    collector."""
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


# ------------------------------------------------------------ the model
def test_layers_go_by_their_types(model):
    assert model.layer_types == TYPES
    assert [layer.is_attention for layer in model.layers] == [
        False, True, False, False, False]
    for layer in model.layers:
        mixer = layer.self_attn if layer.is_attention else layer.conv
        assert isinstance(mixer, Lfm2Attention if layer.is_attention
                          else Lfm2ShortConv)
        assert not hasattr(layer, "conv" if layer.is_attention
                           else "self_attn")
    # the first layer's FFN is dense, the others' the expert layer: the
    # sigmoid router's bias, the source's 1e-6, no shared expert, no loss
    assert isinstance(model.layers[0].feed_forward, LlamaMLP)
    for layer in model.layers[1:]:
        ffn = layer.feed_forward
        assert isinstance(ffn, moe.MoELayer)
        assert ffn.scoring == "sigmoid" and ffn.shared is None
        assert (ffn.aux_weight, ffn.bias_update_speed, ffn.routed_scale,
                ffn.renorm_eps) == (0.0, 0.001, 1.0, 1e-6)
        assert ffn.held == (8, 8) and ffn.top_k == 4
    assert model.layers[1].self_attn.head_dim == 16
    published = lfm2_layer_types(24)
    assert published.count("conv") == 18
    assert [i for i, t in enumerate(published)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    # the cell's five layers: published layer 0, then one whole period
    assert [published[i] for i in (0, 2, 3, 4, 5)] == TYPES
    with pytest.raises(ValueError, match="layer types"):
        Lfm2Model(**model_kwargs(layer_types=TYPES[:3]))
    with pytest.raises(ValueError, match="layer_type"):
        Lfm2DecoderLayer({"hidden_size": 64, "norm_eps": 1e-5},
                         "sliding_attention", dense=True)
    with pytest.raises(ValueError, match="key/value heads"):
        Lfm2Attention(64, num_heads=4, num_kv_heads=3)


def test_the_head_is_the_embedding(model):
    """One parameter, under the embedding's name; the head's ``weight`` is
    its transpose, an ``nn.Linear``'s [hidden, vocab]."""
    names = [n for n, _ in model.named_parameters()]
    assert "embed_tokens.weight" in names
    assert not any(n.startswith("lm_head") for n in names)
    assert model.lm_head.embedding_weight is model.embed_tokens.weight
    assert tuple(model.lm_head.weight.shape) == (64, 256)
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 3, 64)).astype(np.float32))
    np.testing.assert_allclose(
        model.lm_head(x)._value,
        np.asarray(x._value) @ np.asarray(model.embed_tokens.weight._value).T,
        rtol=1e-5, atol=1e-6)


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


@pytest.mark.parametrize("wrong", [dict(rope_theta=1e4),
                                   dict(routed_scaling_factor=2.0),
                                   dict(norm_topk_prob=False),
                                   dict(norm_eps=1e-1)],
                         ids=["theta", "scale", "renorm", "eps"])
def test_another_configuration_is_another_model(model, reference, ids,
                                                wrong):
    """The reference at another RoPE base, routed scale, without the
    renormalisation or at another epsilon is another function of the same
    weights: the comparison would see it."""
    ref = reference.forward(weights(model), ids, SIZES)
    off = reference.forward(weights(model), ids, dict(SIZES, **wrong))
    assert float(jnp.abs(off - ref).max()) > 100 * RTOL * float(
        jnp.abs(ref).max())


def test_amp_o1_stays_near_the_float32_reference(model, reference, ids):
    """bf16 operands, float32 router, norms, RoPE, gates, taps and loss: the
    median token off by bf16's rounding and no more; and it really is bf16
    (further than the float32 comparison allows)."""
    params = model.functional_state()[0]
    logits, total, _, _ = jax.jit(lambda p, a: framework_terms(
        model, p, a, amp=True)[:3] + (None,))(params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    errs = np.asarray(jnp.abs(logits.astype(jnp.float32) - ref[0]).max(
        axis=-1)) / float(jnp.abs(ref[0]).max())
    assert 10 * RTOL < float(np.median(errs)) <= AMP_RTOL
    assert abs(float(total) - float(ref[1])) <= 3e-3 * abs(float(ref[1]))


@pytest.fixture(scope="module")
def gradients(model, reference, ids):
    params, buffers = model.functional_state()
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[1]))(params)
    want = jax.grad(lambda p: reference.loss_terms(
        {**p, **buffers}, ids, SIZES)[0])(params)
    return got, want


#: every kind of parameter the model has, by the end of its name
KINDS = ("embed_tokens.weight", "conv.in_proj.weight", "conv.conv.weight",
         "conv.out_proj.weight", "self_attn.q_proj.weight",
         "self_attn.k_proj.weight", "self_attn.v_proj.weight",
         "self_attn.out_proj.weight", "q_layernorm.weight",
         "k_layernorm.weight", "operator_norm.weight", "ffn_norm.weight",
         "feed_forward.gate_proj.weight", "feed_forward.up_proj.weight",
         "feed_forward.down_proj.weight", "feed_forward.gate.weight",
         "feed_forward.w_gate", "feed_forward.w_up", "feed_forward.w_down",
         "embedding_norm.weight")


@pytest.mark.parametrize("kind", KINDS)
def test_gradients_match_the_reference(gradients, kind):
    """Every parameter of the kind, the tied embedding's among them (its
    gradient is the lookup's and the head's, summed)."""
    got, want = gradients
    assert set(got) == set(want)
    assert all(name.endswith(KINDS) for name in got)
    names = [n for n in got if n.endswith(kind)]
    assert names
    for name in names:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        err = float(jnp.abs(got[name] - want[name]).max())
        assert err <= GRAD_RTOL * scale, (name, err, scale)


# ------------------------------------------------- the short convolution
def _mixer_input(seed, rows=ROWS, seq=SEQ):
    return np.random.default_rng(seed).standard_normal(
        (rows, seq, 64)).astype(np.float32)


@pytest.fixture(scope="module")
def shortconv():
    paddle.seed(3)
    layer = Lfm2ShortConv(64, 3)
    layer.eval()
    return layer


def test_short_convolution_matches_the_reference(shortconv, reference):
    x = _mixer_input(3)
    got = np.asarray(shortconv(paddle.to_tensor(x))._value)
    w = {n: jnp.asarray(v)
         for n, v in shortconv.functional_state()[0].items()}
    want = np.asarray(reference.short_conv(w, jnp.asarray(x), SIZES, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    # the stage's arithmetic by hand: gate, three taps, gate
    bcu = x @ np.asarray(w["in_proj.weight"])
    b, c, u = bcu[..., :64], bcu[..., 64:128], bcu[..., 128:]
    g, taps = b * u, np.asarray(w["conv.weight"])
    mixed = taps[2] * g
    mixed[:, 1:] += taps[1] * g[:, :-1]
    mixed[:, 2:] += taps[0] * g[:, :-2]
    by_hand = (c * mixed) @ np.asarray(w["out_proj.weight"])
    assert np.abs(got - by_hand).max() <= RTOL * np.abs(want).max()


@pytest.mark.parametrize("broken", ["taps-shifted", "gates-swapped",
                                    "rows-joined"])
def test_a_broken_stage_is_another_function(shortconv, broken):
    """What ``tools/lfm2_check.py`` shows to fail on the chip: taps moved by
    a token, B and C swapped, or row 1 fed row 0's tail as history is O(1)
    off the stage."""
    rng = np.random.default_rng(5)
    bcu = jnp.asarray(rng.standard_normal((ROWS, SEQ, 192)), jnp.float32)
    w = shortconv.conv.weight._value
    want = linear_attention.gated_short_conv(bcu, w)
    if broken == "taps-shifted":
        got = linear_attention.gated_short_conv(bcu, jnp.roll(w, 1, axis=0))
    elif broken == "gates-swapped":
        swapped = jnp.concatenate([bcu[..., 64:128], bcu[..., :64],
                                   bcu[..., 128:]], axis=-1)
        got = linear_attention.gated_short_conv(swapped, w)
    else:
        got = linear_attention.gated_short_conv(
            bcu.reshape(1, ROWS * SEQ, 192), w).reshape(ROWS, SEQ, 64)
        # the rows' first two tokens alone differ: the history's reach
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1, 2:], want[1, 2:])
        assert float(jnp.abs(got[1, :2] - want[1, :2]).max()) > 1e-2
        return
    assert float(jnp.abs(got - want).max()) > 0.1 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("moved", [0, 17, 38, 39])
def test_short_convolution_is_causal_and_reaches_three_tokens(shortconv,
                                                              moved):
    """A change at token t of row 0 moves the outputs at t, t + 1 and t + 2
    of THAT row and nothing else: nothing before it (causal), nothing from
    t + 3 on (three taps), nothing in row 1 (a row's history is its own —
    its first tokens see zeros, never row 0's last tokens)."""
    x = _mixer_input(9)
    other = x.copy()
    other[0, moved] += 1.0
    a = np.asarray(shortconv(paddle.to_tensor(x))._value)
    b = np.asarray(shortconv(paddle.to_tensor(other))._value)
    changed = np.abs(a - b).max(axis=-1) > 0
    reach = list(range(moved, min(moved + 3, SEQ)))
    assert np.flatnonzero(changed[0]).tolist() == reach
    assert not changed[1].any()


def test_a_row_is_the_same_alone_and_in_a_batch(shortconv):
    x = _mixer_input(10, rows=3)
    together = np.asarray(shortconv(paddle.to_tensor(x))._value)
    for r in range(3):
        alone = np.asarray(shortconv(paddle.to_tensor(x[r:r + 1]))._value)
        np.testing.assert_allclose(together[r], alone[0], rtol=1e-6,
                                   atol=1e-7)


def test_the_stage_keeps_its_inputs_and_rebuilds_the_float32():
    """Differentiated, the stage's residuals are its two inputs (the bf16
    stream as the projection left it and the taps): no float32 array as
    large as a stream is kept for the backward pass."""
    bcu = jnp.ones((2, 16, 192), jnp.bfloat16)
    w = jnp.ones((3, 64), jnp.float32)
    _, vjp = jax.vjp(linear_attention.gated_short_conv, bcu, w)
    kept = [leaf for leaf in jax.tree_util.tree_leaves(vjp)
            if hasattr(leaf, "shape") and leaf.ndim == 3]
    assert kept and all(leaf.dtype == jnp.bfloat16 for leaf in kept)
    out = linear_attention.gated_short_conv(bcu, w)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 16, 64)


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_attention_layer_matches_the_reference(reference, kernel):
    """The attention module alone, on XLA's route and on the streaming
    kernel (in the Pallas interpreter), against the reference's explicit
    ``h // group`` softmax under an explicit mask: the QK-norms a head,
    full-width RoPE at theta 1e6, query head h on key/value head h // 2, no
    gate."""
    paddle.seed(4)
    layer = Lfm2Attention(64, num_heads=4, num_kv_heads=2)
    rng = np.random.default_rng(4)
    for norm in (layer.q_layernorm, layer.k_layernorm):
        norm.weight.set_value(1 + rng.normal(0, 0.2, 16).astype(np.float32))
    seq = 256 if kernel else SEQ
    x = _mixer_input(4, seq=seq)
    dispatch.evict_ops("flash_attention")
    paddle.set_flags({"pallas_interpret": kernel,
                      "pallas_attention_min_seq": 0 if kernel else 1024})
    route = "stream" if kernel else "xla"
    before = attention._ROUTE_TOTAL.value(route=route)
    try:
        got = np.asarray(layer(paddle.to_tensor(x))._value)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert attention._ROUTE_TOTAL.value(route=route) - before == 1
    w = {n: jnp.asarray(v) for n, v in layer.functional_state()[0].items()}
    sizes = dict(SIZES, reference_q_block=64)
    want = np.asarray(reference.attention(w, jnp.asarray(x), sizes, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    # query heads on the wrong key/value heads (h % 2 for h // 2) is
    # another function of the same weights
    swapped = dict(w)
    for name in ("k_proj.weight", "v_proj.weight"):
        swapped[name] = w[name].reshape(64, 2, 16)[:, ::-1].reshape(64, 32)
    off = np.asarray(reference.attention(swapped, jnp.asarray(x), sizes, ""))
    assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("grads", [False, True], ids=["forward", "backward"])
def test_streaming_kernel_at_heads_of_64(grads):
    """The streaming kernel at the cell's head width (d 64: half a lane
    group a row) in the Pallas interpreter against ``_sdpa_ref``, causal,
    K and V repeated from 2 to 8 heads as the layer hands them over:
    the output, and dq, dk, dv through the one-pass backward."""
    rng = np.random.default_rng(64)
    q = jnp.asarray(rng.standard_normal((2, 8, 256, 64)), jnp.float32)
    k, v = (jnp.repeat(jnp.asarray(rng.standard_normal((2, 2, 256, 64)),
                                   jnp.float32), 4, axis=1)
            for _ in range(2))
    assert flash_attention._one_pass_backward(8192, 64, 2)
    assert flash_attention._stream_block(64, 64, 2) == 1024

    def kernel(q, k, v):
        return flash_attention.mha(q, k, v, causal=True, block_q=128,
                                   block_k=128, interpret=True)

    def plain(q, k, v):
        return attention._sdpa_ref(q, k, v, None, None, scale=0.125,
                                   dropout_p=0.0, is_causal=True)

    if not grads:
        np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                   rtol=2e-5, atol=2e-5)
        return
    cot = jnp.asarray(rng.standard_normal((2, 8, 256, 64)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ the experts
def _expert_layer(held, seed=11):
    paddle.seed(seed)
    layer = moe.MoELayer(
        64, 32, 32, top_k=4, activation="swiglu", gate_bias=False,
        norm_topk_prob=True, scoring="sigmoid", select_bias=True,
        bias_update_speed=0.001, routed_scale=1.0, renorm_eps=1e-6,
        aux_weight=0.0, held=held, held_rows_factor=16.0)
    layer.e_score_correction_bias.set_value(np.random.default_rng(
        seed).normal(0, 0.05, 32).astype(np.float32))
    layer.eval()
    return layer


@pytest.fixture(scope="module")
def whole_layer(reference):
    """The uncut layer's weights, its input, and what the reference gives
    for all 32 experts."""
    whole = _expert_layer(None)
    x = np.random.default_rng(5).standard_normal((2, 16, 64)).astype(
        np.float32)
    state = whole.functional_state()
    w = {k: jnp.asarray(v) for tree in state for k, v in tree.items()}
    sizes = dict(SIZES, held_experts=[0, 32], n_routed_experts=32,
                 held_rows_factor=16.0)
    want, _, dropped, landed, load = reference.experts(
        w, jnp.asarray(x).reshape(32, 64), sizes, "")
    assert int(dropped) == 0 and int(landed) == 32 * 4 == int(load.sum())
    return state, w, x, sizes, np.asarray(want).reshape(2, 16, 64)


def _share(state, first):
    part = _expert_layer((first, 8))
    part.load_functional_state(
        {n: (v[first:first + 8] if n.startswith("w_") else v)
         for n, v in state[0].items()},
        {"e_score_correction_bias": state[1]["e_score_correction_bias"],
         "held_overflow": jnp.zeros((), jnp.int32)})
    return part


@pytest.mark.parametrize("rank", range(4))
def test_a_share_of_eight_experts_matches_the_reference(reference,
                                                        whole_layer, rank):
    """One of the deployment's four chips: ``held=(8 r, 8)`` gives what the
    reference gives when handed the same share."""
    state, w, x, sizes, want = whole_layer
    first = 8 * rank
    part = _share(state, first)
    assert part.resolved_mode() == "sorted_held"
    out = np.asarray(part(paddle.to_tensor(x))._value)
    ref_part = reference.experts(
        {**w, **{n: v[first:first + 8] for n, v in w.items()
                 if n.startswith("w_")}},
        jnp.asarray(x).reshape(32, 64),
        dict(sizes, held_experts=[first, 8]), "")[0]
    assert np.abs(out).max() > 0
    assert np.abs(out - np.asarray(ref_part).reshape(out.shape)).max() <= (
        RTOL * np.abs(want).max())


def test_share_test_four_shares_add_up_to_the_uncut_layer(whole_layer):
    """The guide's share test at this model's layer (sigmoid scores, the
    choice by score + bias, the chosen scores over their sum + 1e-6, no
    shared expert): what the FOUR shares of 8 of 32 experts give adds up to
    what the uncut reference gives for the whole layer — the published
    sizes' own shares, one four-chip host."""
    state, _, x, _, want = whole_layer
    xt = paddle.to_tensor(x)
    total = sum(np.asarray(_share(state, first)(xt)._value)
                for first in range(0, 32, 8))
    assert np.abs(total - want).max() <= RTOL * np.abs(want).max()
    # and the uncut layer on the program's own sorted path gives the same
    assert np.abs(np.asarray(_expert_layer(None)(xt)._value) - want).max() <= (
        RTOL * np.abs(want).max())


def test_the_bias_moves_the_choice_and_never_a_weight(reference):
    """With a bias that lifts an expert no score favours, that expert is
    chosen, and its weight is its own SCORE over the chosen scores' sum +
    1e-6: the bias is in no weight."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    w_router = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    bias = jnp.zeros((32,), jnp.float32).at[31].set(5.0)
    kw = dict(top_k=4, renorm=True, scoring="sigmoid", routed_scale=1.0,
              renorm_eps=1e-6)
    topv, topi, _, _ = moe._route(x, w_router, None, bias, **kw)
    plain_v, plain_i, _, _ = moe._route(x, w_router, None, None, **kw)
    assert bool((topi == 31).any(axis=1).all())
    assert not bool((plain_i == 31).any(axis=1).all())
    scores = jax.nn.sigmoid(x.reshape(8, 64) @ w_router)
    chosen = jnp.take_along_axis(scores, topi, axis=1)
    np.testing.assert_allclose(
        topv, chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6),
        rtol=1e-6)
    np.testing.assert_allclose(plain_v.sum(axis=1), 1.0, rtol=1e-5)
    # the source's 1e-6 is in the weights: 1e-20 gives another sum
    exact = moe._route(x, w_router, None, bias, **dict(kw, renorm_eps=1e-20))
    assert float(jnp.abs(exact[0].sum(axis=1) - 1.0).max()) < float(
        jnp.abs(topv.sum(axis=1) - 1.0).max())


def test_the_bias_update_moves_the_bias_and_no_weight(reference, ids):
    """A traced training forward leaves every expert layer's selection bias
    moved by ``bias_update_speed`` x sign(mean load - load) — the
    reference's rule on the reference's loads — and nothing else; the bias
    gets no gradient, and an eval forward moves nothing."""
    net = build()
    params, buffers = net.functional_state()
    after = jax.jit(lambda p, b: framework_terms(net, p, ids, b)[3])(
        params, buffers)
    w = weights(net)
    h = w["embed_tokens.weight"][ids]
    moved = 0
    for i, layer_type in enumerate(TYPES):
        prefix = f"layers.{i}."
        if i >= 1:
            a = reference._rms(h, w[prefix + "operator_norm.weight"], 1e-5)
            mid = h + (reference.attention(w, a, SIZES, prefix + "self_attn.")
                       if layer_type == "full_attention" else
                       reference.short_conv(w, a, SIZES, prefix + "conv."))
            m = reference._rms(mid, w[prefix + "ffn_norm.weight"], 1e-5)
            load = reference.experts(w, m.reshape(-1, 64), SIZES,
                                     prefix + "feed_forward.")[4]
            name = prefix + "feed_forward.e_score_correction_bias"
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


# ------------------------------------------------------------ the step
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


def test_a_traced_step_counts_once_a_call_site_and_carries_the_scopes(
        residual_counts):
    """With the kernels on (here in the Pallas interpreter) a traced step of
    the five recomputed blocks counts each call site ONCE — four short
    convolutions on their ``xla`` path, one attention core on the ``stream``
    route, four expert layers on the held path —, the one core offers its
    output and log-sum-exp and its block keeps them (the kernel does not run
    twice), and the program carries the scopes that tell the short
    convolution's parts and the attention layer's apart."""
    net = build(use_recompute=True)
    params = net.functional_state()[0]
    ids = jnp.asarray(np.random.default_rng(7).integers(
        0, SIZES["vocab_size"], (2, 256)), jnp.int32)
    dispatch.evict_ops("flash_attention")
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 0})
    try:
        before = residual_counts()
        held = moe._DISPATCH_TOTAL.value(path="sorted_held")
        stream = attention._ROUTE_TOTAL.value(route="stream")
        conv = linear_attention._SHORTCONV_TOTAL.value(path="xla")
        streams = linear_attention._CONV_TOTAL.value(path="xla")
        text = jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text(debug_info=True)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert residual_counts(before) == dict.fromkeys(before, 1)
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") - held == 4
    assert attention._ROUTE_TOTAL.value(route="stream") - stream == 1
    assert linear_attention._SHORTCONV_TOTAL.value(path="xla") - conv == 4
    # linear attention's stage is another counter, and counts nothing here
    assert linear_attention._CONV_TOTAL.value(path="xla") == streams
    assert "rematted_computation" in text
    for scope in ("shortconv.in_proj", "shortconv.stage",
                  "shortconv.out_proj", "lfm2attn.proj", "lfm2attn.qk",
                  "lfm2attn.repeat", "lfm2attn.core", "lfm2attn.out",
                  "Lfm2ShortConv", "Lfm2Attention", "Lfm2DecoderLayer",
                  "operator_norm", "ffn_norm", "moe.experts"):
        assert scope in text, scope
    for name in ("flash_stream_fwd", "flash_stream_bwd_dkv_dq"):
        assert name in text, name


def test_a_train_step_decays_no_norm_weight_and_moves_the_bias(ids):
    """Through ``spmd.build_train_step`` with a learning rate that leaves
    only the decay to see: ``apply_decay_param_fun`` reaches the compiled
    step, so every norm's weight keeps its value where a projection's, the
    taps' and the tied embedding's shrink; the loss is finite, nothing
    overflowed, and the step hands back the moved selection biases."""
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
    # 5 x two block norms + the two QK-norms + the final norm
    assert spared == 5 * 2 + 2 + 1
    assert "lm.embed_tokens.weight" in before
    after = wrapper.functional_state()[1]
    assert all(int(after[n]) == 0 for n in after
               if n.endswith("held_overflow"))
    for name, was in biases.items():
        if name.endswith("e_score_correction_bias"):
            assert np.abs(np.asarray(after[name]) - was).max() == (
                pytest.approx(0.001, rel=1e-3))
