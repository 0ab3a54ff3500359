"""granite-4.0-h-micro (HF ``granitemoehybrid``) on the CPU at a small size
(hidden 64, 8 state-space heads of 16 on a state of 32 — P != N —, one
group, 4 taps with a bias, 4 query heads on 2 key/value heads of 16 at a
scale that is not 16 ** -0.5, a SwiGLU of 96 in every layer, a tied head,
three layers — mamba, attention, mamba; the cell's ten for the counters — 2
rows of 40 tokens, seeded random weights): the chunked state-space scan against the
recurrence, forward and gradients; the framework model against the plain
reference (benchmark/references/granite-4.0-h-micro.py: four shifted
multiply-adds and the bias, the scan as the recurrence over tokens, every
key under an explicit mask, nothing imported from paddle_tpu) in float32 and
under amp O1, forward, loss and every parameter's gradient; the gate before
the norm; the attention module on both routes and the streaming kernel at
heads of 64 and a scale of 1/64 under the interpreter; the three multipliers
and the divisor; the counters and scopes a traced step carries; the
parameters outside weight decay. The same comparison runs at published
widths on the chip (benchmark/configs/granite-4.0-h-micro.py check_train)."""
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
from paddle_tpu.ops import attention, linear_attention
from paddle_tpu.ops.pallas import flash_attention
from paddle_tpu.text import models
from paddle_tpu.text.models import (GraniteAttention,
                                    GraniteHybridDecoderLayer,
                                    GraniteHybridModel, LlamaMLP, Mamba2Mixer,
                                    ZeroCenteredRMSNorm,
                                    granite_hybrid_layer_types, mtp_lm_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the cell's ten layers (the counters' test) and the three that every
#: comparison compiles: both kinds of block, a mamba block on either side
CELL_TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
TYPES = ["mamba", "attention", "mamba"]
SIZES = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 96, "layer_types": TYPES, "mamba_n_heads": 8,
         "mamba_d_head": 16, "mamba_d_state": 32, "mamba_n_groups": 1,
         "mamba_d_conv": 4, "mamba_conv_bias": True,
         "attention_multiplier": 0.03125, "embedding_multiplier": 12.0,
         "residual_multiplier": 0.22, "logits_scaling": 8.0,
         "rms_norm_eps": 1e-5, "initializer_range": 0.1}
ROWS, SEQ = 2, 40

# Both sides compute the same equations in float32 on the CPU, in another
# summation order (chunks against tokens). bf16 arithmetic is off by 1e-3
# and more; the gate after the norm, a bias left out, a scale of d ** -0.5
# or a multiplier in another place by O(1).
RTOL = 2e-5
# gradients sum 80 tokens' contributions through three blocks; compared
# against the largest gradient entry of each parameter
GRAD_RTOL = 2e-4
# amp O1: bf16 operands through three blocks, a share of the largest logit
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
                        "granite-4.0-h-micro.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(seed=47, **over):
    paddle.seed(seed)
    net = GraniteHybridModel(**{"mamba_chunk": 16, "mamba_segment": 32,
                                **SIZES, **over})
    rng = np.random.default_rng(seed)
    for _, sub in net.named_sublayers():
        if isinstance(sub, ZeroCenteredRMSNorm):
            # weights that are not at their start, so that a norm that is
            # left out, or applied on the wrong side of a gate, shows
            sub.weight.set_value(np.asarray(sub.weight._value) + rng.normal(
                0, 0.1, sub.weight.shape).astype(np.float32))
        if isinstance(sub, Mamba2Mixer):
            sub.D.set_value(1 + rng.normal(0, 0.3, sub.D.shape).astype(
                np.float32))
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
def loaded(net, params):
    saved = net.functional_state()
    try:
        with dispatch.trace_mode():
            net.load_functional_state(params, saved[1])
            yield
    finally:
        net.load_functional_state(*saved)


def framework_terms(net, params, ids, amp=False):
    """(logits, loss) as a train step computes them: the cross-entropy on
    the final hidden states (over ``logits_scaling``) and the TIED head's
    weight."""
    with loaded(net, params), auto_cast(enable=amp, level="O1",
                                        dtype="bfloat16"):
        x = Tensor(ids, stop_gradient=True)
        hidden = net.features(x)
        return (net.lm_head(hidden)._value,
                mtp_lm_loss(hidden, [], net.lm_head.weight, x)[0]._value)


def weights(net):
    return dict(net.functional_state()[0])


# ------------------------------------------------------------ the scan
def _scan_inputs(rows, seq, heads, d_head, groups, d_state, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return dict(
        x=normal(rows, seq, heads, d_head),
        dt=jax.nn.softplus(normal(rows, seq, heads) - 1.0),
        a=-jnp.exp(jnp.asarray(rng.uniform(0.0, 2.7, heads), jnp.float32)),
        b=normal(rows, seq, groups, d_state),
        c=normal(rows, seq, groups, d_state), d=normal(heads),
        state=normal(rows, heads, d_state, d_head))


SCANS = {"part-chunks": (37, 1, 8, 16, False),
         "whole-chunks": (64, 1, 16, 32, False),
         "groups-2": (64, 2, 16, 32, True),
         "groups-4-part-segment": (50, 4, 16, 64, True),
         "one-chunk": (16, 1, 16, 16, False),
         "entering-state": (37, 1, 8, 16, True)}


@pytest.mark.parametrize("case", SCANS)
def test_chunked_scan_matches_the_recurrence(case):
    """Forward (y and the final state) and every input's gradient, 2 rows,
    heads of 8 on a state of 16 (P != N), at lengths that are and are not
    whole chunks and segments, one group and several, with and without an
    entering state."""
    seq, groups, chunk, segment, entering = SCANS[case]
    t = _scan_inputs(2, seq, 4, 8, groups, 16, seed=seq + groups)
    state = t["state"] if entering else None
    args = (t["x"], t["dt"], t["a"], t["b"], t["c"], t["d"])

    def chunked(*a):
        return linear_attention.ssd_chunked(*a, state, chunk=chunk,
                                            segment=segment)

    def recurrent(*a):
        return linear_attention.ssd_recurrent(*a, state)

    def with_grads(fn):
        def total(*a):
            out = fn(*a)
            return sum(jnp.sum(o * o) for o in out), out
        return jax.jit(jax.value_and_grad(total, range(6), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, want), wants = with_grads(recurrent)(*args)
        (_, got), grads = with_grads(chunked)(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= RTOL * float(jnp.abs(w).max())
    for g, w in zip(grads, wants):
        assert float(jnp.abs(g - w).max()) <= GRAD_RTOL * float(
            jnp.abs(w).max())


def test_the_scan_is_not_the_delta_rule_with_nothing_written():
    """``beta = 0`` in the gated delta rule writes nothing; the state-space
    scan writes ``B (dt x)^T`` uncorrected: with q = C, k = B, v = dt x and
    beta = 1 the two agree only while the state is empty — at the first
    token."""
    t = _scan_inputs(1, 24, 2, 16, 1, 16, seed=3)
    b, c = (jnp.repeat(t[n], 2, axis=2) for n in ("b", "c"))
    y = linear_attention.ssd_recurrent(t["x"], t["dt"], t["a"], t["b"],
                                       t["c"], jnp.zeros(2))[0]
    g = t["dt"] * t["a"]
    v = t["x"] * t["dt"][..., None]
    silent = linear_attention.kda_recurrent(c, b, v, g, jnp.zeros_like(g))[0]
    assert float(jnp.abs(silent).max()) == 0.0
    delta = linear_attention.kda_recurrent(c, b, v, g, jnp.ones_like(g))[0]
    np.testing.assert_allclose(delta[:, 0], y[:, 0], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(delta[:, 1:] - y[:, 1:]).max()) > 1e-2 * float(
        jnp.abs(y).max())


@contextlib.contextmanager
def interpreter(on=True):
    """The Pallas interpreter selected (it also selects the kernels off a
    TPU), or not."""
    paddle.set_flags({"pallas_interpret": bool(on)})
    try:
        yield
    finally:
        paddle.set_flags({"pallas_interpret": False})


#: seq, heads, d_head, groups, d_state, dtype, the interpreter flag, path —
#: ``ssd_path`` goes by what it can observe: the kernels where the flag (off
#: a TPU) lets a program hold them, the state fills whole lane groups, the
#: values divide or are a multiple of 128 lanes, the operands are bf16 and
#: the row is a token block or more
ENTRIES = {
    "short": (8, 4, 8, 2, 16, jnp.float32, False, "recurrent"),
    "toy-widths": (40, 4, 8, 2, 16, jnp.float32, False, "chunked"),
    "toy-widths-interpreter": (40, 4, 8, 2, 16, jnp.bfloat16, True,
                               "chunked"),
    "cell-widths-no-flag": (256, 8, 64, 1, 128, jnp.bfloat16, False,
                            "chunked"),
    "cell-widths": (256, 8, 64, 1, 128, jnp.bfloat16, True, "kernel"),
    "cell-widths-two-groups": (300, 16, 64, 2, 128, jnp.bfloat16, True,
                               "kernel"),
    "values-of-128": (256, 8, 128, 1, 128, jnp.bfloat16, True, "kernel"),
    "float32": (256, 8, 64, 1, 128, jnp.float32, True, "chunked"),
    "under-a-token-block": (255, 8, 64, 1, 128, jnp.bfloat16, True,
                            "chunked"),
    "short-at-cell-widths": (8, 8, 64, 1, 128, jnp.bfloat16, True,
                             "recurrent"),
    "half-a-lane-group-of-state": (256, 8, 64, 1, 64, jnp.bfloat16, True,
                                   "chunked"),
    "values-across-lane-groups": (256, 8, 96, 1, 128, jnp.bfloat16, True,
                                  "chunked"),
}


@pytest.mark.parametrize("case", ENTRIES)
def test_the_entry_point_picks_by_length_and_counts(case):
    """``ssd_scan`` on Tensors: streams [B, T, H P] and [B, T, G N] in, a
    stream out, the same numbers as heads in and out; the path from the
    length, the widths, the operand dtype and what ``ops.placement`` lets
    the program hold (never a flag or an argument of its own), one count a
    call and none under another label."""
    seq, heads, d_head, groups, d_state, dtype, flag, path = ENTRIES[case]
    t = _scan_inputs(2, seq, heads, d_head, groups, d_state, seed=seq)
    t = dict(t, **{n: t[n].astype(dtype) for n in "xbc"})
    names = ("x", "dt", "a", "b", "c", "d")
    labels = ("kernel", "chunked", "recurrent")
    before = {p: linear_attention._SSD_TOTAL.value(path=p) for p in labels}
    flat = dict(t, x=t["x"].reshape(2, seq, -1), b=t["b"].reshape(2, seq, -1),
                c=t["c"].reshape(2, seq, -1))
    with interpreter(flag):
        assert linear_attention.ssd_path(seq, heads, groups, d_head, d_state,
                                         dtype) == path
        heads_out = linear_attention.ssd_scan(
            *(paddle.to_tensor(np.asarray(t[n])) for n in names), chunk=16,
            segment=32)
        streams = linear_attention.ssd_scan(
            *(paddle.to_tensor(np.asarray(flat[n])) for n in names),
            groups=groups, chunk=16, segment=32)
    for p in labels:
        assert linear_attention._SSD_TOTAL.value(path=p) - before[p] == (
            2 * (p == path)), p
    assert tuple(streams.shape) == (2, seq, heads * d_head)
    assert streams._value.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(streams._value.astype(jnp.float32)).reshape(
            2, seq, heads, d_head),
        np.asarray(heads_out._value.astype(jnp.float32)))
    want = linear_attention.ssd_recurrent(*(t[n].astype(jnp.float32)
                                            for n in names))[0]
    assert float(jnp.abs(heads_out._value.astype(jnp.float32)
                         - want).max()) <= (
        RTOL if dtype == jnp.float32 else AMP_RTOL) * float(
            jnp.abs(want).max())
    assert linear_attention.ssd_path(linear_attention.SUB) == "chunked"


def test_the_kernel_path_needs_a_platform_and_known_devices(monkeypatch):
    """No TPU and no interpreter flag: the XLA scan. A platform that
    compiles the kernels but a program whose devices are not known (a plain
    jit on several devices): the XLA scan again. ``use_pallas_kernels`` off:
    the XLA scan whatever else holds."""
    from paddle_tpu.ops import placement

    shape = (8192, 64, 1, 64, 128, jnp.bfloat16)
    assert linear_attention.ssd_path(*shape) == "chunked"
    monkeypatch.setattr(placement, "is_tpu_available", lambda: True)
    assert jax.device_count() > 1                       # conftest's mesh
    assert linear_attention.ssd_path(*shape) == "chunked"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert linear_attention.ssd_path(*shape) == "kernel"
    paddle.set_flags({"use_pallas_kernels": False})
    try:
        assert linear_attention.ssd_path(*shape) == "chunked"
    finally:
        paddle.set_flags({"use_pallas_kernels": True})


def test_the_kernel_path_keeps_the_heads_whole_over_an_announced_mesh():
    """Inside a step traced for a dp2 x mp2 mesh (``topology.tracing_for``)
    the path is still ``kernel`` and the call runs under ``placement
    .on_mesh``'s ``shard_map``: rows over the data axis, the heads WHOLE on
    both devices of 'mp' (B and C are one group's for all heads: no spec
    names 'mp'), A and D a copy a row; the result and the gradients of A
    and D are the one-device ones."""
    t = _scan_inputs(2, 256, 8, 64, 1, 128, seed=21)
    args = tuple(t[n].astype(jnp.bfloat16) if n in "xbc" else t[n]
                 for n in ("x", "dt", "a", "b", "c", "d"))
    args = (args[0].reshape(2, 256, -1), args[1], args[2],
            args[3].reshape(2, 256, -1), args[4].reshape(2, 256, -1), args[5])
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    def scan(*a):
        return linear_attention._ssd_kernel_output(*a, groups=1,
                                                   interpret=True)

    def step(*a):
        with topology.tracing_for(mesh):
            assert linear_attention.ssd_path(
                256, 8, 1, 64, 128, jnp.bfloat16) == "kernel"
            return scan(*a)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            fn(*a).astype(jnp.float32) ** 2), argnums=(2, 5)))(*args)

    with interpreter():
        text = jax.jit(step).lower(*args).as_text()
        assert "shard_map" in text or "manual" in text
        jaxpr = str(jax.make_jaxpr(step)(*args))
        assert "shard_map" in jaxpr and "'mp'" not in jaxpr.split(
            "shard_map")[1].split("jaxpr=")[0]
        got, want = jax.jit(step)(*args), scan(*args)
        assert got.shape == (2, 256, 512) and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(want.astype(jnp.float32)),
                                   rtol=1e-6, atol=1e-6)
        for g, w in zip(grads(step), grads(scan)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)


def test_group_sizes_that_do_not_divide_are_refused():
    t = _scan_inputs(1, 16, 4, 8, 3, 16)
    with pytest.raises(ValueError, match="groups"):
        linear_attention.ssd_chunked(t["x"], t["dt"], t["a"], t["b"], t["c"],
                                     t["d"])
    with pytest.raises(ValueError, match="groups"):
        Mamba2Mixer(64, num_heads=8, n_groups=3)


# ------------------------------------------------------------ the mixer
@pytest.fixture(scope="module")
def mixer():
    paddle.seed(5)
    layer = Mamba2Mixer(64, num_heads=8, head_dim=16, d_state=32, chunk=16,
                        segment=32)
    rng = np.random.default_rng(5)
    layer.norm.weight.set_value(1 + rng.normal(0, 0.2, 128).astype(
        np.float32))
    layer.D.set_value(1 + rng.normal(0, 0.3, 8).astype(np.float32))
    layer.eval()
    return layer


def _mixer_input(seed, rows=ROWS, seq=SEQ):
    return np.random.default_rng(seed).standard_normal(
        (rows, seq, 64)).astype(np.float32)


def _mixer_weights(layer):
    return {n: jnp.asarray(v)
            for n, v in layer.functional_state()[0].items()}


def test_mixer_matches_the_reference_and_starts_as_mamba2(mixer, reference):
    x = _mixer_input(5)
    got = np.asarray(mixer(paddle.to_tensor(x))._value)
    w = _mixer_weights(mixer)
    want = np.asarray(reference.mamba(w, jnp.asarray(x), SIZES, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    assert set(w) == {"in_proj.weight", "conv1d.weight", "conv1d.bias",
                      "A_log", "dt_bias", "D", "norm.weight",
                      "out_proj.weight"}
    assert w["in_proj.weight"].shape == (64, 128 + 192 + 8)
    assert w["conv1d.weight"].shape == (4, 192)
    assert w["conv1d.bias"].shape == (192,)
    # the bias starts as torch's depthwise Conv1d starts it, not at 0
    assert 0 < float(jnp.abs(w["conv1d.bias"]).max()) <= 0.5
    # Mamba-2's own start: A in [1, 16], dt in [1e-3, 1e-1] behind the
    # softplus
    fresh = Mamba2Mixer(64, num_heads=8, head_dim=16, d_state=32)
    a = np.exp(np.asarray(fresh.A_log._value))
    dt = np.asarray(jax.nn.softplus(fresh.dt_bias._value))
    assert (1 <= a).all() and (a <= 16).all()
    assert (1e-3 * 0.999 <= dt).all() and (dt <= 1e-1 * 1.001).all()
    np.testing.assert_array_equal(np.asarray(fresh.D._value), np.ones(8))


@pytest.mark.parametrize("broken", ["gate-after-norm", "no-conv-bias"])
def test_a_broken_mixer_is_another_function(mixer, reference, monkeypatch,
                                            broken):
    """What ``tools/granite_check.py`` shows to fail on the chip. The gate
    comes BEFORE the norm: the order the repo's other gated norms have (norm,
    then gate) is O(1) off; so is the convolution without its bias."""
    x = _mixer_input(6)
    w = _mixer_weights(mixer)
    want = np.asarray(reference.mamba(w, jnp.asarray(x), SIZES, ""))
    if broken == "gate-after-norm":
        def gate_after(y, z, w, *, eps):
            yf = y.astype(jnp.float32)
            return (yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True)
                                       + eps) * w * jax.nn.silu(z))

        monkeypatch.setattr(models, "_mamba_gated_norm", gate_after)
    else:
        streams = models._mamba_streams
        monkeypatch.setattr(
            models, "_mamba_streams",
            lambda xbc, w, bias, **kw: streams(xbc, w, jnp.zeros_like(bias),
                                               **kw))
    for op in ("mamba_gated_norm", "mamba_streams"):
        dispatch.evict_ops(op)
    try:
        got = np.asarray(mixer(paddle.to_tensor(x))._value)
    finally:
        for op in ("mamba_gated_norm", "mamba_streams"):
            dispatch.evict_ops(op)
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_a_row_starts_from_nothing_whatever_the_row_before_held(mixer):
    """Row 1 of a batch starts from a zero state and a zero convolution
    history: it is the same alone and behind any row 0."""
    x = _mixer_input(8)
    both = np.asarray(mixer(paddle.to_tensor(x))._value)
    alone = np.asarray(mixer(paddle.to_tensor(x[1:]))._value)
    other = np.asarray(mixer(paddle.to_tensor(
        np.concatenate([_mixer_input(9)[:1] * 5, x[1:]])))._value)
    np.testing.assert_allclose(both[1], alone[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(other[1], alone[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("moved", [0, 17, 39])
def test_mixer_is_causal(mixer, moved):
    x = _mixer_input(10)
    y = x.copy()
    y[:, moved] += 1.0
    a = np.asarray(mixer(paddle.to_tensor(x))._value)
    b = np.asarray(mixer(paddle.to_tensor(y))._value)
    np.testing.assert_array_equal(a[:, :moved], b[:, :moved])
    assert np.abs(a[:, moved:] - b[:, moved:]).max() > 0


# ------------------------------------------------------------ the model
def test_layers_go_by_their_types(model):
    assert model.layer_types == TYPES
    assert [layer.is_attention for layer in model.layers] == [
        t == "attention" for t in TYPES]
    for layer in model.layers:
        mixer = layer.self_attn if layer.is_attention else layer.mamba
        assert isinstance(mixer, GraniteAttention if layer.is_attention
                          else Mamba2Mixer)
        assert not hasattr(layer, "mamba" if layer.is_attention
                           else "self_attn")
        # every layer's feed-forward part is one dense SwiGLU
        assert isinstance(layer.shared_mlp, LlamaMLP)
    published = granite_hybrid_layer_types(40)
    assert [i for i, t in enumerate(published) if t == "attention"] == [
        5, 15, 25, 35]
    assert published[:10] == CELL_TYPES
    with pytest.raises(ValueError, match="layer types"):
        GraniteHybridModel(**dict(SIZES, layer_types=TYPES[:2]))
    with pytest.raises(ValueError, match="layer_type"):
        GraniteHybridDecoderLayer({"hidden_size": 64, "rms_norm_eps": 1e-5,
                                   "residual_multiplier": 0.22}, "conv")
    with pytest.raises(ValueError, match="key/value heads"):
        GraniteAttention(64, num_heads=4, num_kv_heads=3)


def test_the_head_is_the_embedding(model):
    names = [n for n, _ in model.named_parameters()]
    assert "embed_tokens.weight" in names
    assert not any(n.startswith("lm_head") for n in names)
    assert model.lm_head.embedding_weight is model.embed_tokens.weight


def test_logits_and_loss_match_the_reference(model, reference, ids):
    params = model.functional_state()[0]
    logits, loss = jax.jit(lambda p, a: framework_terms(model, p, a))(
        params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    scale = float(jnp.abs(ref[0]).max())
    assert float(jnp.abs(logits - ref[0]).max()) <= RTOL * scale
    assert abs(float(loss) - float(ref[1])) <= RTOL * abs(float(ref[1]))


#: a multiplier or the divisor at another value: where the equations put it
MOVED = {"embedding_multiplier": 6.0, "residual_multiplier": 0.5,
         "logits_scaling": 4.0, "attention_multiplier": 0.25}


@pytest.mark.parametrize("key", MOVED)
def test_each_multiplier_moves_the_output_where_the_equations_say(
        model, reference, ids, key):
    """The model built with another embedding multiplier, residual
    multiplier, logits divisor or attention scale is the reference's
    function at that value, and not at the published one."""
    moved = build(**{key: MOVED[key]})
    logits = jax.jit(lambda p: framework_terms(moved, p, ids)[0])(
        moved.functional_state()[0])
    there = reference.forward(weights(moved), ids, dict(SIZES, **{
        key: MOVED[key]}))
    here = reference.forward(weights(moved), ids, SIZES)
    scale = float(jnp.abs(there).max())
    assert float(jnp.abs(logits - there).max()) <= RTOL * scale
    assert float(jnp.abs(logits - here).max()) > 100 * RTOL * scale


def test_amp_o1_stays_near_the_float32_reference(model, reference, ids):
    params = model.functional_state()[0]
    logits, loss = jax.jit(lambda p, a: framework_terms(
        model, p, a, amp=True))(params, ids)
    ref = reference.outputs(weights(model), ids, SIZES)
    errs = np.asarray(jnp.abs(logits.astype(jnp.float32) - ref[0]).max(
        axis=-1)) / float(jnp.abs(ref[0]).max())
    assert 10 * RTOL < float(np.median(errs)) <= AMP_RTOL
    assert abs(float(loss) - float(ref[1])) <= 3e-3 * abs(float(ref[1]))


@pytest.fixture(scope="module")
def gradients(model, reference, ids):
    params = model.functional_state()[0]
    got = jax.jit(jax.grad(
        lambda p: framework_terms(model, p, ids)[1]))(params)
    want = jax.grad(lambda p: reference.loss(p, ids, SIZES))(params)
    return got, want


#: every kind of parameter the model has, by the end of its name
KINDS = ("embed_tokens.weight", "mamba.in_proj.weight",
         "mamba.conv1d.weight", "mamba.conv1d.bias", "mamba.A_log",
         "mamba.dt_bias", "mamba.D", "mamba.norm.weight",
         "mamba.out_proj.weight", "self_attn.q_proj.weight",
         "self_attn.k_proj.weight", "self_attn.v_proj.weight",
         "self_attn.o_proj.weight", "input_layernorm.weight",
         "post_attention_layernorm.weight", "shared_mlp.gate_proj.weight",
         "shared_mlp.up_proj.weight", "shared_mlp.down_proj.weight",
         "norm.weight")


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


# ------------------------------------------------------------ attention
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_attention_layer_matches_the_reference(reference, kernel):
    """The attention module alone, on XLA's route and on the streaming
    kernel (in the Pallas interpreter), against the reference's explicit
    ``h // group`` softmax under an explicit mask at ``attention_multiplier``
    — nothing rotated, no norm a head, query head h on key/value head
    h // 2."""
    paddle.seed(4)
    layer = GraniteAttention(64, num_heads=4, num_kv_heads=2,
                             attention_multiplier=0.03125)
    seq = 256 if kernel else SEQ
    x = _mixer_input(4, seq=seq) * 4
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
    w = _mixer_weights(layer)
    assert set(w) == {f"{n}_proj.weight" for n in "qkvo"}
    sizes = dict(SIZES, reference_q_block=64)
    want = np.asarray(reference.attention(w, jnp.asarray(x), sizes, ""))
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max()
    # an explicit h // 2 softmax at the multiplier, by hand
    q, k, v = (np.asarray(jnp.asarray(x) @ w[f"{n}_proj.weight"]).reshape(
        ROWS, seq, -1, 16) for n in "qkv")
    mask = np.tril(np.ones((seq, seq), bool))
    heads = []
    for h in range(4):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // 2]) * 0.03125
        p = np.exp(np.where(mask, s, -np.inf) - s.max(-1, keepdims=True))
        heads.append(np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                               v[:, :, h // 2]))
    by_hand = np.concatenate(heads, -1) @ np.asarray(w["o_proj.weight"])
    assert np.abs(got - by_hand).max() <= 5 * RTOL * np.abs(want).max()
    # at 16 ** -0.5 for the multiplier it is another function
    off = np.asarray(reference.attention(
        w, jnp.asarray(x), dict(sizes, attention_multiplier=0.25), ""))
    assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("grads", [False, True], ids=["forward", "backward"])
def test_streaming_kernel_at_heads_of_64_and_a_scale_of_its_own(grads):
    """The streaming kernel at the cell's head width (d 64) and scale (1/64,
    not 1/8) in the Pallas interpreter against ``_sdpa_ref``, causal, K and V
    repeated from 2 to 8 heads as the layer hands them over: the output, and
    dq, dk, dv through the one-pass backward."""
    rng = np.random.default_rng(64)
    q = jnp.asarray(rng.standard_normal((1, 8, 256, 64)) * 4, jnp.float32)
    k, v = (jnp.repeat(jnp.asarray(rng.standard_normal((1, 2, 256, 64)),
                                   jnp.float32), 4, axis=1)
            for _ in range(2))

    def kernel(q, k, v):
        return flash_attention.mha(q, k, v, causal=True, scale=1 / 64,
                                   block_q=128, block_k=128, interpret=True)

    def plain(q, k, v, scale=1 / 64):
        return attention._sdpa_ref(q, k, v, None, None, scale=scale,
                                   dropout_p=0.0, is_causal=True)

    if not grads:
        np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                   rtol=2e-5, atol=2e-5)
        assert float(jnp.abs(plain(q, k, v, 0.125) - plain(q, k, v)).max()
                     ) > 1e-2
        return
    cot = jnp.asarray(rng.standard_normal((1, 8, 256, 64)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ the step
def test_recomputation_gives_the_same_loss_and_gradients(ids):
    plain, remat = build(use_recompute=False), build(use_recompute=True)
    params = plain.functional_state()[0]

    def loss_and_grads(net):
        return jax.jit(jax.value_and_grad(
            lambda p: framework_terms(net, p, ids)[1]))(params)

    loss_a, grads_a = loss_and_grads(plain)
    loss_b, grads_b = loss_and_grads(remat)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    for name in grads_a:
        scale = float(jnp.abs(grads_a[name]).max())
        assert float(jnp.abs(grads_a[name] - grads_b[name]).max()) <= (
            1e-5 * scale), name


def test_the_scan_kernels_carry_a_step_under_the_interpreter():
    """The model at the cell's scan widths (8 heads of 64 on a state of
    128, a row of 256 tokens) under amp O1: with the interpreter flag the
    two state-space layers take the kernels — ``paddle_tpu_ssd_core_total
    {path="kernel"}`` counts once a traced call site, ``chunked`` nothing —
    and loss and gradients are the ``chunked`` path's within a bf16
    rounding; under per-block ``recompute`` the kernels give the same loss
    and gradients again, still one count a site, and the program holds
    both calls in their inner jits under ``mamba.core``."""
    sizes = dict(mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128)
    plain = build(use_recompute=False, **sizes)
    remat = build(use_recompute=True, **sizes)
    params = plain.functional_state()[0]
    ids = jnp.asarray(np.random.default_rng(11).integers(
        0, SIZES["vocab_size"], (1, 256)), jnp.int32)
    labels = ("kernel", "chunked", "recurrent")

    def run(net, flag):
        before = {p: linear_attention._SSD_TOTAL.value(path=p)
                  for p in labels}
        with interpreter(flag):
            step = jax.jit(jax.value_and_grad(
                lambda p: framework_terms(net, p, ids, amp=True)[1]))
            text = step.lower(params).as_text(debug_info=True)
            loss, grads = step(params)
        counted = {p: linear_attention._SSD_TOTAL.value(path=p) - before[p]
                   for p in labels}
        return loss, grads, counted, text

    loss_x, grads_x, counted, text = run(plain, False)
    # two state-space layers, each traced once
    assert counted == {"kernel": 0, "chunked": 2, "recurrent": 0}
    assert "ssd_chunk_fwd" not in text
    loss_k, grads_k, counted, text = run(plain, True)
    assert counted == {"kernel": 2, "chunked": 0, "recurrent": 0}
    for jitted, name in (("_forward", "ssd_chunk_fwd"),
                         ("_backward", "ssd_chunk_bwd")):
        assert f"mamba.core/jit({jitted})" in text, jitted
        assert f"{name}/pallas_call" in text, name
    assert float(loss_k) == pytest.approx(float(loss_x), rel=3e-3)
    for name in grads_x:
        scale = float(jnp.abs(grads_x[name]).max())
        assert float(jnp.abs(grads_k[name] - grads_x[name]).max()) <= (
            AMP_RTOL * scale), name
    loss_r, grads_r, counted, text = run(remat, True)
    assert counted == {"kernel": 2, "chunked": 0, "recurrent": 0}
    assert "rematted_computation" in text
    assert float(loss_r) == pytest.approx(float(loss_k), rel=1e-5)
    for name in grads_k:
        scale = float(jnp.abs(grads_k[name]).max())
        assert float(jnp.abs(grads_r[name] - grads_k[name]).max()) <= (
            1e-3 * scale), name


def test_a_traced_step_counts_once_a_call_site_and_carries_the_scopes(
        residual_counts):
    """With the kernels on (here in the Pallas interpreter) a traced step of
    the cell's ten recomputed blocks counts each call site ONCE — nine scans on
    their ``chunked`` path, nine biased convolution stages on ``kernel`` (a
    state of the published 128, so that x | B | C fill whole lane groups) and
    none on ``xla``, one attention core on the ``stream`` route —, the one
    core offers its output and log-sum-exp and its block keeps them, and the
    program carries the scopes that tell the state-space mixer's parts and
    the attention layer's apart, the stage's two calls under ``mamba.conv``."""
    net = build(use_recompute=True, num_hidden_layers=10,
                layer_types=CELL_TYPES, mamba_d_state=128)
    params = net.functional_state()[0]
    ids = jnp.asarray(np.random.default_rng(7).integers(
        0, SIZES["vocab_size"], (1, 256)), jnp.int32)
    dispatch.evict_ops("flash_attention")
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 0})
    try:
        before = residual_counts()
        stream = attention._ROUTE_TOTAL.value(route="stream")
        scans = linear_attention._SSD_TOTAL.value(path="chunked")
        convs = linear_attention._CONV_TOTAL.value(path="xla")
        kernels = linear_attention._CONV_TOTAL.value(path="kernel")
        delta = {p: linear_attention._CORE_TOTAL.value(path=p)
                 for p in ("chunked", "chunked_scalar", "kernel_scalar")}
        text = jax.jit(jax.grad(
            lambda p: framework_terms(net, p, ids)[1])).lower(
                params).as_text(debug_info=True)
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})
    assert residual_counts(before) == dict.fromkeys(before, 1)
    assert attention._ROUTE_TOTAL.value(route="stream") - stream == 1
    assert linear_attention._SSD_TOTAL.value(path="chunked") - scans == 9
    assert linear_attention._CONV_TOTAL.value(path="kernel") - kernels == 9
    assert linear_attention._CONV_TOTAL.value(path="xla") == convs
    # the delta rule's counter counts nothing here
    assert delta == {p: linear_attention._CORE_TOTAL.value(path=p)
                     for p in delta}
    assert "rematted_computation" in text
    for scope in ("mamba.in_proj", "mamba.conv", "mamba.dt", "mamba.core",
                  "mamba.norm", "mamba.out_proj", "gattn64.proj",
                  "gattn64.repeat", "gattn64.core", "gattn64.out",
                  "Mamba2Mixer", "GraniteAttention",
                  "GraniteHybridDecoderLayer", "input_layernorm",
                  "post_attention_layernorm", "shared_mlp"):
        assert scope in text, scope
    for name in ("flash_stream_fwd", "flash_stream_bwd_dkv_dq"):
        assert name in text, name
    # the stage's calls sit in inner jits, entered under the stage's scope
    for jitted, name in (("_conv_forward", "conv_streams_fwd"),
                         ("_conv_backward", "conv_streams_bwd")):
        assert f"mamba.conv/jit({jitted})" in text, jitted
        assert f"{name}/pallas_call" in text, name


def test_a_train_step_decays_no_scan_parameter_and_no_norm_weight(ids):
    """Through ``spmd.build_train_step`` with a learning rate that leaves
    only the decay to see: ``apply_decay_param_fun`` reaches the compiled
    step, so ``A_log``, ``dt_bias``, ``D`` and every norm's weight keep
    their values where a projection's, the taps', the convolution's bias
    and the tied embedding's shrink."""
    net = build(use_recompute=True)

    class Wrapper(paddle.nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, x):
            return self.lm.features(x), self.lm.lm_head.weight

    wrapper = Wrapper(net)
    wrapper.train()
    no_decay = ("A_log", "dt_bias", ".D", "norm_weight")
    opt = optimizer.AdamW(
        1e-2, parameters=net.parameters(), weight_decay=0.5, epsilon=1e30,
        apply_decay_param_fun=lambda n: not n.endswith(no_decay))
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
        if "norm" in name or name.endswith(("A_log", "dt_bias", ".D")):
            np.testing.assert_array_equal(now, was)
            spared += 1
        else:
            np.testing.assert_allclose(now, was * (1 - 1e-2 * 0.5),
                                       rtol=1e-5, atol=1e-9)
    # 3 x two block norms + 2 x (A_log, dt_bias, D, the gated norm) + the
    # final norm
    assert spared == 3 * 2 + 2 * 4 + 1
    assert "lm.embed_tokens.weight" in before
    assert "lm.layers.0.mamba.conv1d.bias" in before
