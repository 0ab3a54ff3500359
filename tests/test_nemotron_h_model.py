"""NVIDIA-Nemotron-3-Super (HF ``nemotron_h``) on the CPU at a small size
(hidden 64; layers that are ONE sublayer each, ``MEM*E`` and an MTP module
of ``*E``; 8 state-space heads of 16 on a state of 32 in 4 groups, 8 query
heads of 16 on 2 key/value heads, 32 relu² experts of 48 in a latent space
of 32, top-4, a relu² shared expert of 96; 2 rows of 40 tokens, seeded
random weights): the framework model — every layer told which heads or
experts it holds — against the plain reference
(benchmark/references/nemotron-3-super-120b-a12b.py: nothing imported from
paddle_tpu) in float32 and under amp O1, logits, both loss terms and every
parameter's gradient; THE SHARES ADD UP for the state-space mixer, the
attention and the LatentMoE layer; a held mixer is the whole mixer's slice
bit for bit; relu² and the latent width on the held path against dense
routing; the gated norm a group; the MTP module on the block it is given;
the counters and scopes a traced step carries; a step through
``spmd.build_train_step``. The same comparison runs at published widths on
the chip (benchmark/configs/nemotron-3-super-120b-a12b.py check_train)."""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.amp.auto_cast import auto_cast
from paddle_tpu.core import dispatch
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.incubate import moe
from paddle_tpu.ops import linear_attention
from paddle_tpu.text import models
from paddle_tpu.text.models import (JoyAIDecoderLayer, JoyAIFlashModel,
                                    Mamba2Mixer, MultiTokenPredictor,
                                    NemotronAttention, NemotronHLayer,
                                    NemotronHModel, RMSNorm, Relu2MLP,
                                    mtp_lm_loss, nemotron_layer_types)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN, MTP_PATTERN = "MEM*E", "*E"
#: the whole layers' counts, and the share the model under test holds:
#: groups 1-2 of 4 (heads 2-5 of 8), key/value head 1 of 2 (query heads
#: 4-7 of 8), experts 8-15 of 32
WHOLE = {"mamba_num_heads": 8, "n_groups": 4, "num_attention_heads": 8,
         "num_key_value_heads": 2, "n_routed_experts": 32}
HELD = {"held_mamba_heads": (2, 4), "held_attention_heads": (4, 4),
        "held_experts": (8, 8)}
MODEL = {"vocab_size": 256, "hidden_size": 64, "head_dim": 16,
         "mamba_head_dim": 16, "ssm_state_size": 32, "mamba_chunk": 16,
         "mamba_segment": 32, "moe_intermediate_size": 48,
         "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
         "num_experts_per_tok": 4, "initializer_range": 0.1,
         "hybrid_override_pattern": PATTERN,
         "mtp_hybrid_override_pattern": MTP_PATTERN}
#: what the reference reads: the HELD counts
SIZES = {"hidden_size": 64, "head_dim": 16, "num_hidden_layers": 5,
         "layer_types": nemotron_layer_types(PATTERN),
         "mtp_hybrid_override_pattern": MTP_PATTERN,
         "num_nextn_predict_layers": 1, "layer_norm_epsilon": 1e-5,
         "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 32,
         "mamba_n_groups": 2, "num_attention_heads": 4,
         "num_key_value_heads": 1, "router_experts": 32,
         "held_experts": [8, 8], "held_rows_factor": 2.0,
         "num_experts_per_tok": 4, "norm_topk_prob": True,
         "routed_scaling_factor": 5.0, "moe_latent_size": 32,
         "mtp_loss_weight": 0.1, "reference_q_block": 8}
#: the same reference given everything: the UNCUT layers
UNCUT = dict(SIZES, mamba_n_heads=8, mamba_n_groups=4, num_attention_heads=8,
             num_key_value_heads=2, held_experts=[0, 32],
             held_rows_factor=32.0)
ROWS, SEQ = 2, 40

# Both sides compute the same equations in float32 on the CPU, in another
# summation order (chunks against tokens, sorted rows against dense
# routing). bf16 arithmetic is off by 1e-3 and more; a gate after the norm,
# a norm over all groups, relu for relu², a missing latent projection or a
# rotated head by O(1).
RTOL = 2e-5
# gradients sum 80 tokens' contributions through seven layers; compared
# against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4
# amp O1: bf16 operands through seven layers, a share of the largest logit,
# at the median token (a token whose held expert swaps under bf16 moves, and
# with it every later token of its row: the chip's check compares the
# decided ones layer by layer)
AMP_RTOL = 3e-2
LOSS_AMP_RTOL = 5e-3


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "nemotron-3-super-120b-a12b.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unsettle(net, seed):
    """Norm weights, ``D`` and the routers' biases off their start, so that
    a norm left out or laid over the wrong features, a skip dropped or a
    bias added to a weight shows."""
    rng = np.random.default_rng(seed)
    for _, sub in net.named_sublayers(include_self=True):
        if isinstance(sub, (RMSNorm, models.ZeroCenteredRMSNorm)):
            sub.weight.set_value(np.asarray(sub.weight._value) + rng.normal(
                0, 0.1, sub.weight.shape).astype(np.float32))
        if isinstance(sub, Mamba2Mixer):
            sub.D.set_value(1 + rng.normal(0, 0.3, sub.D.shape).astype(
                np.float32))
        if (isinstance(sub, moe.MoELayer)
                and sub.e_score_correction_bias is not None):
            sub.e_score_correction_bias.set_value(rng.normal(
                0, 0.02, [sub.num_experts]).astype(np.float32))
    return net


def build(seed=51, **over):
    paddle.seed(seed)
    net = unsettle(NemotronHModel(**{**MODEL, **WHOLE, **HELD, **over}), seed)
    net.train()
    return net


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(7).integers(
        0, MODEL["vocab_size"], (ROWS, SEQ)), jnp.int32)


@contextlib.contextmanager
def loaded(net, params):
    saved = net.functional_state()
    try:
        with dispatch.trace_mode():
            net.load_functional_state(params, saved[1])
            yield
    finally:
        net.load_functional_state(*saved)


def framework_terms(net, params, ids, amp=False):
    """(main logits, MTP logits, total, main term, MTP term) as a train
    step computes them: both cross-entropies on the final hidden states and
    the one shared head's weight."""
    with loaded(net, params), auto_cast(enable=amp, level="O1",
                                        dtype="bfloat16"):
        x = Tensor(ids, stop_gradient=True)
        hidden, mtp_hidden = net.training_features(x)
        total, main, mtp = mtp_lm_loss(hidden, mtp_hidden, net.lm_head.weight,
                                       x, SIZES["mtp_loss_weight"])
        return (net.lm_head(hidden)._value,
                net.lm_head(mtp_hidden[0])._value, total._value, main._value,
                mtp._value)


def weights(net):
    params, buffers = net.functional_state()
    return {**params, **{n: v for n, v in buffers.items()
                         if n.endswith("e_score_correction_bias")}}


def rel(got, ref):
    return float(np.abs(np.asarray(got, np.float32) - np.asarray(ref)).max()
                 / np.abs(np.asarray(ref)).max())


# ------------------------------------------------- model against reference
def test_layers_are_one_sublayer_each_by_the_pattern(model):
    assert model.layer_types == ["mamba", "moe", "mamba", "attention", "moe"]
    for layer, kind in zip(model.layers, model.layer_types):
        assert isinstance(layer, NemotronHLayer) and layer.kind == kind
        # a norm and ONE mixer: no second sublayer, no second norm
        assert [n for n, _ in layer.named_children()] == ["norm", "mixer"]
    kinds = {"mamba": Mamba2Mixer, "attention": NemotronAttention,
             "moe": moe.MoELayer}
    assert all(isinstance(layer.mixer, kinds[layer.kind])
               for layer in model.layers)
    module = model.mtp[0]
    assert [sub.kind for sub in module.block] == ["attention", "moe"]
    # the head is untied and the module shares it and the embedding
    assert model.lm_head.weight is not model.embed_tokens.weight
    assert not any("embed" in n or "lm_head" in n
                   for n, _ in module.named_parameters())
    with pytest.raises(ValueError, match="none of"):
        nemotron_layer_types("MEX")
    assert isinstance(NemotronHLayer(
        {"hidden_size": 64, "layer_norm_epsilon": 1e-5,
         "intermediate_size": 48}, "mlp").mixer, Relu2MLP)


def test_logits_and_both_losses_match_the_reference(model, reference, ids):
    got = jax.jit(lambda p, a: framework_terms(model, p, a))(
        model.functional_state()[0], ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    assert int(ref[5]) == 0                      # no held pair dropped
    assert rel(got[0], ref[0]) < RTOL
    assert rel(got[1], ref[1][0]) < RTOL
    for g, r in zip(got[2:], ref[2:5]):
        assert abs(float(g) - float(r)) / abs(float(r)) < RTOL
    # the two terms are not one: lambda weighs the second
    assert float(ref[2]) == pytest.approx(
        float(ref[3]) + SIZES["mtp_loss_weight"] * float(ref[4]), rel=1e-6)


def test_amp_o1_stays_near_the_float32_reference(model, reference, ids):
    got = jax.jit(lambda p, a: framework_terms(model, p, a, amp=True))(
        model.functional_state()[0], ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    for g, r in ((got[0], ref[0]), (got[1], ref[1][0])):
        errs = np.abs(np.asarray(g, np.float32) - np.asarray(r)).max(-1)
        assert 1e-4 < np.median(errs) / np.abs(np.asarray(r)).max() < AMP_RTOL
    for g, r in zip(got[2:], ref[2:5]):
        assert abs(float(g) - float(r)) / abs(float(r)) < LOSS_AMP_RTOL


@pytest.fixture(scope="module")
def gradients(model, reference, ids):
    params = model.functional_state()[0]
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[2]))(params)
    extra = {n: v for n, v in weights(model).items() if n not in params}
    ref = jax.jit(jax.grad(lambda w: reference.loss(
        {**w, **extra}, ids, SIZES)))(dict(params))
    return params, got, ref


KINDS = ["embed_tokens", "lm_head", "A_log", "dt_bias", ".D", "in_proj",
         "conv1d", "out_proj", "q_proj", "k_proj", "o_proj", "gate.weight",
         "latent_down", "latent_up", "w_up", "w_down", "shared", "norm",
         "eh_proj", "mtp.0.block"]


@pytest.mark.parametrize("kind", KINDS)
def test_gradients_match_the_reference(gradients, kind):
    params, got, ref = gradients
    names = [n for n in params if kind in n]
    assert names
    for name in names:
        scale = float(jnp.abs(ref[name]).max())
        assert scale > 0, name
        assert float(jnp.abs(got[name] - ref[name]).max()) / scale < (
            GRAD_RTOL), name


def test_recomputation_gives_the_same_loss_and_gradients(model, ids):
    plain = build()
    again = build(use_recompute=True)
    params = plain.functional_state()[0]

    def loss_and_grads(net):
        return jax.jit(jax.value_and_grad(
            lambda p: framework_terms(net, p, ids)[2]))(params)

    (l0, g0), (l1, g1) = loss_and_grads(plain), loss_and_grads(again)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    for name in g0:
        np.testing.assert_allclose(g1[name], g0[name], rtol=1e-4, atol=1e-7)


# ------------------------------------------------------ the shares add up
def _x(seed=3, rows=ROWS, seq=SEQ, hidden=64):
    return paddle.to_tensor(np.random.default_rng(seed).normal(
        0, 1, (rows, seq, hidden)).astype(np.float32))


def _value(t):
    return np.asarray(t._value)


def mamba_share_columns(whole, held):
    """Where a share ``held=(first, count)`` of a whole mixer lives: the
    indices of its heads, of its ``inner`` features (``out_proj``'s rows,
    the norm's weights), of its convolution channels and of ``in_proj``'s
    columns, each in the share's own order [z | x | B | C | dt]."""
    first, count = held
    per_group = whole.num_heads // whole.n_groups
    heads = np.arange(first, first + count)
    inner = np.arange(first * whole.head_dim, (first + count) * whole.head_dim)
    group = np.arange(first // per_group * whole.d_state,
                      (first + count) // per_group * whole.d_state)
    state = whole.n_groups * whole.d_state
    conv = np.concatenate([inner, whole.inner + group,
                           whole.inner + state + group])
    return {"heads": heads, "inner": inner, "conv": conv,
            "in_proj": np.concatenate([
                inner, whole.inner + conv,
                whole.inner + whole.conv_dim + heads])}


def take_mamba_share(share, whole):
    """Load the share's slices of the whole mixer's parameters."""
    at = mamba_share_columns(whole, share.held_heads)
    share.in_proj.weight.set_value(
        _value(whole.in_proj.weight)[:, at["in_proj"]])
    share.conv1d.weight.set_value(_value(whole.conv1d.weight)[:, at["conv"]])
    share.conv1d.bias.set_value(_value(whole.conv1d.bias)[at["conv"]])
    for name in ("A_log", "dt_bias", "D"):
        getattr(share, name).set_value(
            _value(getattr(whole, name))[at["heads"]])
    share.norm.weight.set_value(_value(whole.norm.weight)[at["inner"]])
    share.out_proj.weight.set_value(
        _value(whole.out_proj.weight)[at["inner"]])


def take_attention_share(share, whole):
    """Load the share's query heads' columns of ``q_proj`` and rows of
    ``o_proj``, and the key/value head(s) they read."""
    first, count = share.held_heads
    per_kv, d = whole.num_heads // whole.num_kv_heads, whole.head_dim
    q = np.arange(first * d, (first + count) * d)
    kv = np.arange(first // per_kv * d,
                   ((first + count - 1) // per_kv + 1) * d)
    share.q_proj.weight.set_value(_value(whole.q_proj.weight)[:, q])
    share.k_proj.weight.set_value(_value(whole.k_proj.weight)[:, kv])
    share.v_proj.weight.set_value(_value(whole.v_proj.weight)[:, kv])
    share.o_proj.weight.set_value(_value(whole.o_proj.weight)[q])


def _mixer(held=None, groups=4, seed=11):
    paddle.seed(seed)
    net = unsettle(Mamba2Mixer(64, num_heads=8, head_dim=16, d_state=32,
                               n_groups=groups, chunk=16, segment=32,
                               held_heads=held), seed)
    net.train()
    return net


def _mamba_sizes(heads, groups):
    return dict(SIZES, mamba_n_heads=heads, mamba_n_groups=groups)


def _state(net, prefix):
    return {prefix + n: v for n, v in net.functional_state()[0].items()}


def test_the_state_space_shares_add_up_to_the_uncut_layer(reference):
    """Four shares of two heads (one group each) of a mixer of 8 heads in 4
    groups: every share's partial sum after ``out_proj`` adds up to the
    whole mixer's output and to the uncut reference's."""
    whole, x = _mixer(), _x()
    want = np.asarray(whole(x)._value)
    ref = reference.mamba(_state(whole, "m."), x._value,
                          _mamba_sizes(8, 4), "m.")
    assert rel(want, ref) < RTOL
    total = 0.0
    for first in range(0, 8, 2):
        share = _mixer(held=(first, 2))
        take_mamba_share(share, whole)
        assert (share.num_heads, share.n_groups) == (2, 1)
        part = np.asarray(share(x)._value)
        # a share is what the reference gives for the same share
        assert rel(part, reference.mamba(_state(share, "m."), x._value,
                                         _mamba_sizes(2, 1), "m.")) < RTOL
        total = total + part
    assert rel(total, want) < RTOL
    assert rel(total, ref) < RTOL


@pytest.mark.parametrize("held", [(0, 2), (2, 4), (4, 4)])
def test_a_held_mixer_is_the_whole_mixers_slice_bit_for_bit(held):
    """What ``out_proj`` multiplies — the heads' gated, normed outputs — is
    a head's (a group's) own function of x: the share's equal the whole
    mixer's at its own features, bit for bit."""
    whole, x = _mixer(), _x()
    share = _mixer(held=held)
    take_mamba_share(share, whole)
    at = mamba_share_columns(whole, held)["inner"]
    np.testing.assert_array_equal(
        np.asarray(share.heads_output(x)._value),
        np.asarray(whole.heads_output(x)._value)[..., at])


def test_the_gated_norm_is_a_groups(reference):
    """With more than one group the mean square is over a group's features:
    another function than Granite's one mean square over all of them."""
    mixer, x = _mixer(), _x()
    one = _mixer(groups=1)
    one.set_state_dict({n: v for n, v in mixer.state_dict().items()
                        if "in_proj" not in n and "conv1d" not in n})
    y = jnp.asarray(np.random.default_rng(1).normal(0, 1, (2, 8, 128)),
                    jnp.float32)
    z, w = y[::-1] * 0.5, jnp.linspace(0.5, 1.5, 128)
    grouped = models._mamba_gated_norm(y, z, w, eps=1e-5, groups=4)
    g = (y * jax.nn.silu(z)).reshape(2, 8, 4, 32)
    want = (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
            ).reshape(2, 8, 128) * w
    np.testing.assert_allclose(grouped, want, rtol=1e-6, atol=1e-6)
    whole = models._mamba_gated_norm(y, z, w, eps=1e-5)
    assert rel(grouped, whole) > 1e-2
    assert mixer.n_groups == 4 and one.n_groups == 1


def _attention(held=None, seed=13):
    paddle.seed(seed)
    net = NemotronAttention(64, num_heads=8, num_kv_heads=2, head_dim=16,
                            held_heads=held)
    net.train()
    return net


def _attn_sizes(heads, kv):
    return dict(SIZES, num_attention_heads=heads, num_key_value_heads=kv)


@pytest.mark.parametrize("count", [4, 2, 1], ids=["a-kv-head", "half", "one"])
def test_the_attention_shares_add_up_to_the_uncut_layer(reference, count):
    """Shares of whole key/value heads' queries (4 of 8 on 1 of 2), and of
    a part of one's (2 or 1 query heads on the key/value head they read):
    the partial sums after ``o_proj`` add up to the whole layer's output
    and to the uncut reference's."""
    whole, x = _attention(), _x()
    want = np.asarray(whole(x)._value)
    ref = reference.attention(_state(whole, "a."), x._value,
                              _attn_sizes(8, 2), "a.")
    assert rel(want, ref) < RTOL
    total = 0.0
    for first in range(0, 8, count):
        share = _attention(held=(first, count))
        take_attention_share(share, whole)
        assert (share.num_heads, share.num_kv_heads) == (count, 1)
        part = np.asarray(share(x)._value)
        assert rel(part, reference.attention(
            _state(share, "a."), x._value, _attn_sizes(count, 1),
            "a.")) < RTOL
        total = total + part
    assert rel(total, want) < RTOL
    assert rel(total, ref) < RTOL


def test_attention_rotates_nothing_and_is_causal():
    attn, x = _attention(), _x()
    out = np.asarray(attn(x)._value)
    # no positions: a row whose tokens before t are permuted gives token t
    # the same output
    perm = np.arange(SEQ)
    perm[:10] = perm[:10][::-1]
    moved = np.asarray(attn(paddle.to_tensor(
        np.asarray(x._value)[:, perm]))._value)
    np.testing.assert_allclose(moved[:, 10:], out[:, 10:], rtol=1e-4,
                               atol=1e-6)
    # causal: a later token moves no earlier output
    later = np.asarray(x._value).copy()
    later[:, 30:] += 1.0
    np.testing.assert_allclose(
        np.asarray(attn(paddle.to_tensor(later))._value)[:, :30],
        out[:, :30], rtol=1e-5, atol=1e-6)


def _latent_moe(held=None, seed=17, **over):
    paddle.seed(seed)
    kw = dict(ffn_hidden=48, num_experts=32, top_k=4, activation="relu2",
              latent_size=32, gate_bias=False, scoring="sigmoid",
              select_bias=True, bias_update_speed=0.001, routed_scale=5.0,
              shared_width=96, held=held, held_rows_factor=32.0,
              aux_weight=0.0)
    net = unsettle(moe.MoELayer(64, **{**kw, **over}), seed)
    net.eval()
    return net


def _take_experts(share, whole):
    """The share's range of the whole layer's experts, and everything every
    chip holds alike."""
    first, count = share.held
    state = {n: np.asarray(v._value) for n, v in whole.state_dict().items()}
    for name in ("w_up", "w_down"):
        state[name] = state[name][first:first + count]
    state.pop("held_overflow", None)
    share.set_state_dict({n: v for n, v in state.items()
                          if n in share.state_dict()})


def _moe_state(net, prefix):
    return {prefix + n: np.asarray(v._value)
            for n, v in net.state_dict().items()}


def test_the_latent_moe_shares_add_up_to_the_uncut_layer(reference):
    """Four shares of 8 of the 32 experts: each share's routed part (its
    output less the shared expert's, which every chip computes alike and is
    counted once) adds up, with the shared expert's once, to the layer that
    holds every expert on the sorted path, and to the uncut reference's
    dense routing. The down-projection is every share's own input and the
    up-projection is linear, so the shares' routed outputs simply add."""
    whole, x = _latent_moe(), _x(rows=1)
    assert whole.resolved_mode() == "sorted"
    want = np.asarray(whole(x)._value)
    tokens = x._value.reshape(-1, 64)
    ref, margin, dropped, landed = reference.experts(
        _moe_state(whole, "e."), tokens, UNCUT, "e.")
    assert int(dropped) == 0 and int(landed) == SEQ * 4
    assert rel(want.reshape(-1, 64), ref) < RTOL
    shared = np.asarray(whole.shared(x)._value)
    total = shared
    for first in range(0, 32, 8):
        share = _latent_moe(held=(first, 8))
        _take_experts(share, whole)
        assert share.resolved_mode() == "sorted_held"
        part = np.asarray(share(x)._value)
        sizes = dict(SIZES, held_experts=[first, 8], held_rows_factor=32.0)
        assert rel(part.reshape(-1, 64), reference.experts(
            _moe_state(share, "e."), tokens, sizes, "e.")[0]) < RTOL
        total = total + (part - shared)
    assert rel(total, want) < RTOL
    assert rel(total.reshape(-1, 64), ref) < RTOL


def test_relu2_in_the_latent_space_on_the_held_path_with_gradients(
        reference):
    """The held path's two grouped matmuls with relu² between, 32 wide in
    and out, against dense routing over the held experts: the output and
    the gradients of the input and of every parameter."""
    share, x = _latent_moe(held=(8, 8)), _x(rows=1)
    share.train()
    params, buffers = share.functional_state()

    def layer_loss(p, a):
        saved = share.functional_state()
        try:
            with dispatch.trace_mode():
                share.load_functional_state(p, buffers)
                y = share(Tensor(a))._value
        finally:
            share.load_functional_state(*saved)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def ref_loss(p, a):
        w = {"e." + n: v for n, v in {**p, **buffers}.items()}
        y = reference.experts(w, a.reshape(-1, 64), dict(
            SIZES, held_rows_factor=32.0), "e.")[0].reshape(a.shape)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    (_, got), g_got = jax.jit(jax.value_and_grad(
        layer_loss, argnums=(0, 1), has_aux=True))(params, x._value)
    (_, ref), g_ref = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1), has_aux=True))(dict(params), x._value)
    assert rel(got, ref) < RTOL
    assert rel(g_got[1], g_ref[1]) < GRAD_RTOL
    for name in params:
        assert rel(g_got[0][name], g_ref[0][name]) < GRAD_RTOL, name
    # relu² is not relu, gelu or SwiGLU: the experts hold two matrices 32
    # wide in and out, the shared expert two on the hidden-wide stream
    assert share.w_gate is None and isinstance(share.shared, Relu2MLP)
    assert tuple(share.w_up.shape) == (8, 32, 48)
    assert tuple(share.w_down.shape) == (8, 48, 32)
    assert tuple(share.latent_down.weight.shape) == (64, 32)
    assert tuple(share.gate.weight.shape) == (64, 32)
    with pytest.raises(ValueError, match="'gelu', 'relu2' or 'swiglu'"):
        moe.MoELayer(64, 48, 8, activation="relu")


@pytest.mark.parametrize("over", [
    dict(dispatch_mode="dense"), dict(dispatch_mode="capacity"),
    dict(dispatch_mode="dense", activation="gelu"),
    dict(dispatch_mode="dense", latent_size=0)],
    ids=["dense", "capacity", "latent-alone", "relu2-alone"])
def test_relu2_and_the_latent_space_run_on_the_sorted_paths_alone(over):
    """The paths that run every expert as batched einsums have gelu or
    SwiGLU experts on the hidden-wide stream: asked for relu² or a latent
    space they say so, they do not run another function."""
    layer = _latent_moe(scoring="softmax", select_bias=False, shared_width=0,
                        routed_scale=1.0, num_experts=8, top_k=2, **over)
    with pytest.raises(NotImplementedError, match="sorted paths"):
        layer(_x(rows=1))


# --------------------------------------------------------------- the module
def test_the_mtp_module_wraps_the_block_it_is_given():
    paddle.seed(5)
    block = nn.Sequential(nn.Linear(64, 64), nn.Linear(64, 64))
    module = MultiTokenPredictor(64, 1e-5, block)
    assert module.block is block
    built = MultiTokenPredictor(64, 1e-5, lambda: nn.Linear(64, 64))
    assert isinstance(built.block, nn.Linear)
    h, emb = _x(1), _x(2)
    out, normed = module(h, emb)
    x = module.eh_proj(paddle.concat([module.hnorm(h), module.enorm(emb)],
                                     axis=-1))
    np.testing.assert_allclose(out._value, block(x)._value, rtol=1e-6)
    np.testing.assert_allclose(normed._value, module.norm(out)._value,
                               rtol=1e-6)


def test_joyais_module_starts_as_it_did():
    """JoyAI's model builds its expert block inside the module, after
    ``eh_proj``: the same parameter names in the same order, so a seed gives
    every parameter the value it had."""
    kw = dict(vocab_size=64, hidden_size=32, num_hidden_layers=1,
              num_attention_heads=2, intermediate_size=48,
              moe_intermediate_size=16, n_routed_experts=8,
              num_experts_per_tok=2, q_lora_rank=16, kv_lora_rank=8,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    paddle.seed(9)
    net = JoyAIFlashModel(**kw)
    names = [n for n, _ in net.mtp[0].named_parameters()]
    assert names[:3] == ["hnorm.weight", "enorm.weight", "eh_proj.weight"]
    assert names[-1] == "norm.weight"
    assert isinstance(net.mtp[0].block, JoyAIDecoderLayer)
    assert all(n.startswith("block.") for n in names[3:-1])
    # the values a seed gives: the module built by hand in the old order
    paddle.seed(9)
    again = JoyAIFlashModel(**kw)
    for (n, a), (_, b) in zip(net.named_parameters(),
                              again.named_parameters()):
        np.testing.assert_array_equal(a._value, b._value, err_msg=n)


# ------------------------------------------------- counters, scopes, a step
def test_a_traced_step_counts_once_a_call_site_and_carries_the_scopes(
        model, ids):
    layers = moe._LAYER_TOTAL.value(activation="relu2", latent="32")
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    scans = linear_attention._SSD_TOTAL.value(path="chunked")
    convs = linear_attention._CONV_TOTAL.value(path="xla")
    lowered = jax.jit(lambda p, a: framework_terms(model, p, a)[2]).lower(
        model.functional_state()[0], ids)
    # two expert layers and the module's, two state-space layers
    assert moe._LAYER_TOTAL.value(activation="relu2",
                                  latent="32") == layers + 3
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") == held + 3
    assert linear_attention._SSD_TOTAL.value(path="chunked") == scans + 2
    assert linear_attention._CONV_TOTAL.value(path="xla") == convs + 2
    text = lowered.as_text(debug_info=True)
    for scope in ("latentmoe.down", "latentmoe.up", "moe.shared",
                  "moe.route", "moe.experts", "moe.dispatch", "moe.combine",
                  "nattn.qkv", "nattn.core", "nattn.out", "mamba.in_proj",
                  "mamba.conv", "mamba.core", "mamba.norm", "mamba.out_proj",
                  "MultiTokenPredictor", "NemotronHLayer"):
        assert scope in text, scope
    # the state-space scopes are siblings, as Granite's readers read them
    assert "mamba.out_proj/mamba." not in text


def test_a_train_step_runs_and_spares_what_is_not_decayed(ids):
    """Through ``spmd.build_train_step`` (recompute a layer, amp O1): the
    loss falls, no held pair is dropped, the routers' biases move against
    the loads, and ``A_log``, ``dt_bias``, ``D`` and every norm's weight
    keep out of weight decay."""
    net = build(use_recompute=True)

    class Wrapper(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            hidden, mtp_hidden = self.lm.training_features(x)
            return hidden, tuple(mtp_hidden), self.lm.lm_head.weight

    wrapper = Wrapper(net)
    wrapper.train()
    no_decay = ("A_log", "dt_bias", ".D", "norm_weight")
    opt = optimizer.AdamW(
        3e-3, parameters=net.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda n: not (
            n.endswith(no_decay) or n.startswith("rmsnorm_")))
    spared = [p.name for p in net.parameters()
              if not opt._apply_decay_param_fun(p.name)]
    # 5 + 2 layer norms, the final norm, the module's three, and (A_log,
    # dt_bias, D, the gated norm) of two mixers
    assert len(spared) == 7 + 1 + 3 + 2 * 4
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step, init = spmd.build_train_step(
        wrapper, lambda out, y: mtp_lm_loss(
            out[0], list(out[1]), out[2], y, 0.1)[0]._value,
        opt, mesh=mesh, amp_level="O1", donate=False)
    params, opt_state = init()
    losses = []
    for _ in range(6):
        loss, params, opt_state = step(params, opt_state, ids, ids)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    buffers = wrapper.functional_state()[1]
    overflow = [v for n, v in buffers.items() if n.endswith("held_overflow")]
    assert len(overflow) == 3 and all(int(v) == 0 for v in overflow)
    biases = [np.asarray(v) for n, v in buffers.items()
              if n.endswith("e_score_correction_bias")]
    assert len(biases) == 3 and all(np.abs(b).max() > 0.02 for b in biases)
