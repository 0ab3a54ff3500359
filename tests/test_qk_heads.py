"""``ops.attention.qk_heads``: softmax attention's stage between the
projections and the core — a head's RMSNorm of q and of k, rotate-half RoPE,
the split into [B, heads, T, d]. The Mosaic kernels (``ops/pallas/
qk_heads.py``, here in the Pallas interpreter) against the XLA stage — what
every other program runs — forward and the stated VJP over everything the
two stems that call it differ in; which path a stage takes and the counter
that says so; the stage under an announced mesh; and the layers that call
it (Trinity's two attention types against the configuration's float32
reference, Qwen3-Next's gated attention against its own XLA stage)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.ops import attention, placement
from paddle_tpu.ops.pallas import qk_heads as kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, KV_HEADS = 4, 2
#: float32 agreement over 500 positions and more: an angle of 500 radians
#: is exact to 3e-5, and a jitted and an eager trace round it differently
ANGLES = 1e-4


@pytest.fixture
def interpreter():
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags({"pallas_interpret": False})


@pytest.fixture(autouse=True)
def _no_global_mesh():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    yield
    topology.set_global_mesh(saved)


def close(got, want, rtol):
    got, want = jnp.asarray(got), jnp.asarray(want)
    scale = float(jnp.abs(want).max())
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= rtol * scale, (err, scale)


def one_bf16_ulp(got, want):
    """Every element of ``got`` (bf16) within one bf16 step of the float32
    ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    assert np.isfinite(got).all()
    assert (np.abs(got - want) <= step + 1e-6 * np.abs(want).max()).all()


def stage_inputs(seed, d, stride, dtype, zero_centered, batch=2, seq=80):
    """Streams, the two norms' weights (about 1, or about 0 where the norm
    adds 1) and cotangents of the head arrays."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (batch, seq, HEADS * stride * d))
    k = jax.random.normal(keys[1], (batch, seq, KV_HEADS * d))
    w_q, w_k = (jax.random.normal(key, (d,)) * 0.3 + (not zero_centered)
                for key in keys[2:4])
    c_q = jax.random.normal(keys[4], (batch, HEADS, seq, d))
    c_k = jax.random.normal(keys[5], (batch, KV_HEADS, seq, d))
    return tuple(x.astype(dtype) for x in (q, k)), (w_q, w_k), tuple(
        x.astype(dtype) for x in (c_q, c_k))


def the_kernels(q, k, w_q, w_k, *, d, tokens=32, lanes=None, **static):
    """The kernels as the op calls them (the weights laid on every head's
    lanes a batch row), at blocks small enough that a row is several of
    them and the last one padded."""
    def rows(w, n):
        return jnp.broadcast_to(jnp.tile(w, n)[None, None],
                                (q.shape[0], 1, n * d))

    return kernels.qk_heads(q, k, rows(w_q, HEADS), rows(w_k, KV_HEADS), d=d,
                            tokens=tokens, lanes=lanes, interpret=True,
                            **static)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("zero_centered", [False, True],
                         ids=["weight", "one-plus-weight"])
@pytest.mark.parametrize("rotary_dim", [None, "all", 64],
                         ids=["no-rope", "rope", "rope-64"])
def test_the_kernels_are_the_xla_stage(rotary_dim, zero_centered, d, stride,
                                       dtype):
    """Forward and the VJP (dq, dk and both weights' gradients) against the
    XLA stage in float32 on the same values: batch 2, 80 tokens in blocks
    of 32 (the last one padded), four query heads on two key heads — at
    128-wide heads two query heads a step and a key head each step, at 256
    (and wherever the query's columns alternate with a gate's) a query head
    a step and a key head every second one. Float32 to 1e-5; bf16 results
    (q, k, dq, dk) within one bf16 step of the float32 stage's, the weights'
    gradients — float32 sums either way — to 1e-5 of float32 streams' and
    to bf16's rounding of the cotangents otherwise."""
    (q, k), (w_q, w_k), cotangents = stage_inputs(31, d, stride, dtype,
                                                  zero_centered)
    turned = d if rotary_dim == "all" else rotary_dim
    static = dict(zero_centered=zero_centered, eps=1e-6, base=1e4,
                  stride=stride)
    f32 = jnp.float32
    want, want_vjp = jax.vjp(
        lambda *a: attention._qk_xla(
            *a, heads=HEADS, kv_heads=KV_HEADS, d=d, rotary_dim=turned,
            **static), q.astype(f32), k.astype(f32), w_q, w_k)
    want_grads = want_vjp(tuple(c.astype(f32) for c in cotangents))
    got, got_vjp = jax.vjp(
        lambda *a: the_kernels(*a, d=d, lanes=256, rotary_dim=turned,
                               **static), q, k, w_q, w_k)
    got_grads = got_vjp(cotangents)
    assert kernels.qk_steps(HEADS, KV_HEADS, d, stride, 256) == (
        2 if d == 128 and stride == 1 else 4)
    assert [o.shape for o in got] == [(2, HEADS, 80, d), (2, KV_HEADS, 80, d)]
    for a, b in zip(got + got_grads[:2], want + want_grads[:2]):
        assert a.dtype == dtype and a.shape == b.shape
        if dtype == jnp.bfloat16:
            one_bf16_ulp(a, b)
        else:
            close(a, b, 1e-5)
    for a, b in zip(got_grads[2:], want_grads[2:]):
        assert a.dtype == f32 and a.shape == (d,)
        close(a, b, 1e-5 if dtype == f32 else 2e-2)
    if stride > 1:
        # the gate's columns of the q stream took no part
        gates = got_grads[0].reshape(2, 80, HEADS, stride * d)[..., d:]
        assert not np.asarray(gates, np.float32).any()


def test_the_rotation_is_rotate_half_of_the_first_features():
    """The kernels' tables against ``rotary``'s definition, with no norm in
    the way (unit rows, weight 1 would still rescale: so on the XLA stage's
    own output): position 0 stays, a rotation keeps a head's norm, and the
    features past ``rotary_dim`` pass."""
    (q, k), (w_q, w_k), _ = stage_inputs(32, 128, 1, jnp.float32, False)
    static = dict(d=128, zero_centered=False, eps=1e-6, base=1e4)
    plain = the_kernels(q, k, w_q, w_k, rotary_dim=None, **static)
    full = the_kernels(q, k, w_q, w_k, rotary_dim=128, **static)
    part = the_kernels(q, k, w_q, w_k, rotary_dim=64, **static)
    for a, b, c in zip(plain, full, part):
        close(b, attention.rotary(a, 1e4, pairing="half"), 1e-5)
        close(c, attention.rotary(a, 1e4, pairing="half", rotary_dim=64),
              1e-5)
        np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])
        np.testing.assert_array_equal(a[..., 64:], c[..., 64:])
        np.testing.assert_allclose(jnp.linalg.norm(b, axis=-1),
                                   jnp.linalg.norm(a, axis=-1), rtol=1e-5)


# ------------------------------------------------------------- the path
@pytest.mark.parametrize("seq, heads, kv_heads, d, dtype, rotary_dim, path", [
    (16384, 32, 4, 128, jnp.bfloat16, 128, "kernel"),    # Trinity, sliding
    (16384, 32, 4, 128, jnp.bfloat16, None, "kernel"),   # Trinity, full
    (16384, 16, 2, 256, jnp.bfloat16, 64, "kernel"),     # Qwen3-Next
    (512, 32, 4, 128, jnp.float32, 128, "kernel"),
    (8192, 32, 8, 64, jnp.bfloat16, 64, "xla"),          # LFM2: half a group
    (511, 32, 4, 128, jnp.bfloat16, 128, "xla"),         # under one block
    (16384, 32, 4, 128, jnp.float16, 128, "xla"),
    (16384, 32, 5, 128, jnp.bfloat16, 128, "xla"),       # no whole groups
    (16384, 16, 2, 256, jnp.bfloat16, 192, "xla"),       # past a lane group
    (16384, 16, 2, 256, jnp.bfloat16, 63, "xla"),
])
def test_the_path_goes_by_widths_dtype_and_length(
        interpreter, seq, heads, kv_heads, d, dtype, rotary_dim, path):
    assert attention.qk_path(seq, heads, kv_heads, d, dtype,
                             rotary_dim) == path


def test_the_path_needs_a_platform_and_known_devices(monkeypatch):
    """No TPU and no interpreter flag: the XLA stage. A platform that
    compiles the kernels but a program whose devices are not known: the XLA
    stage again. Under an announced mesh an 'mp' axis may cut both streams
    between whole heads, not through a key head."""
    shape = (16384, 32, 4, 128, jnp.bfloat16, 128)
    assert attention.qk_path(*shape) == "xla"
    monkeypatch.setattr(placement, "is_tpu_available", lambda: True)
    assert jax.device_count() > 1                       # conftest's mesh
    assert attention.qk_path(*shape) == "xla"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert attention.qk_path(*shape) == "kernel"
    for axes, path in ((dict(dp=4), "kernel"), (dict(dp=2, mp=2), "kernel"),
                       (dict(mp=4), "kernel"),
                       (dict(mp=8), "xla")):     # half a key head a shard
        mesh = topology.build_mesh(
            **axes, devices=jax.devices()[:int(np.prod(list(axes.values())))])
        with topology.tracing_for(mesh):
            assert attention.qk_path(*shape) == path, axes


@pytest.mark.parametrize("seq, d, path", [(512, 128, "kernel"),
                                          (600, 128, "kernel"),
                                          (500, 128, "xla"),
                                          (512, 64, "xla")])
def test_the_stage_counts_the_path_once_a_trace(interpreter, seq, d, path):
    """One count a traced call, under the label of the path taken, and
    either path's result is the XLA stage's."""
    keys = jax.random.split(jax.random.PRNGKey(33), 4)
    q = jax.random.normal(keys[0], (1, seq, HEADS * d))
    k = jax.random.normal(keys[1], (1, seq, KV_HEADS * d))
    w_q, w_k = (jax.random.normal(key, (d,)) * 0.2 + 1.0 for key in keys[2:])
    static = dict(heads=HEADS, kv_heads=KV_HEADS, zero_centered=False,
                  eps=1e-5, rope=True, base=1e4)
    before = {p: attention._QK_TOTAL.value(path=p) for p in ("kernel", "xla")}
    stage = jax.jit(lambda *a: attention.qk_heads(
        *a, kernel=attention.qk_kernel(a[0], HEADS, KV_HEADS, d, d),
        **static))
    got = stage(q, k, w_q, w_k)
    stage(q, k, w_q, w_k)                           # traced once
    for p, n in before.items():
        assert attention._QK_TOTAL.value(path=p) == n + (p == path), p
    want = attention._qk_xla(q, k, w_q, w_k, heads=HEADS, kv_heads=KV_HEADS,
                             d=d, stride=1, zero_centered=False, eps=1e-5,
                             base=1e4, rotary_dim=d)
    for a, b in zip(got, want):
        close(a, b, ANGLES)


def _shard_map_specs(fn, args):
    """The (in_specs, out_specs) of the first shard_map ``fn`` traces."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                return eqn.params["in_specs"], eqn.params["out_specs"]
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                found = find(inner) if hasattr(inner, "eqns") else None
                if found is not None:
                    return found
        return None

    return find(jax.make_jaxpr(fn)(*args).jaxpr)


def test_the_kernels_shard_over_an_announced_mesh(interpreter):
    """Inside a step traced for a mesh the stage runs under the attention
    kernels' ``shard_map``: rows over the data axis and heads over 'mp' —
    dim 2 of the streams and of the weights' rows going in, dim 1 of the
    head arrays coming out; the weights' gradient is summed over the
    shards. Result and gradients are the one-device ones."""
    (q, k), weights, cotangents = stage_inputs(34, 128, 1, jnp.float32,
                                               False, seq=512)
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    static = dict(heads=HEADS, kv_heads=KV_HEADS, zero_centered=False,
                  eps=1e-6, rope=True, base=1e4)

    def loss(stage):
        return lambda *a: sum(jnp.sum(o * c)
                              for o, c in zip(stage(*a), cotangents))

    def on_mesh(*a):
        with topology.tracing_for(mesh):
            return attention.qk_heads(*a, kernel=attention.qk_kernel(
                a[0], HEADS, KV_HEADS, 128, 128), **static)

    ins, outs = _shard_map_specs(on_mesh, (q, k, *weights))
    P = jax.sharding.PartitionSpec
    # both streams and both weights' rows cut both ways, and nothing else
    # goes in (no seed; the tables are made inside)
    assert list(map(str, ins)) == [str(P("dp", None, "mp"))] * 4, ins
    assert list(map(str, outs)) == [str(P("dp", "mp", None))] * 2, outs
    want = jax.value_and_grad(loss(lambda *a: attention._qk_xla(
        *a, heads=HEADS, kv_heads=KV_HEADS, d=128, stride=1,
        zero_centered=False, eps=1e-6, base=1e4, rotary_dim=128)),
        argnums=(0, 1, 2, 3))(q, k, *weights)
    got = jax.jit(jax.value_and_grad(loss(on_mesh), argnums=(0, 1, 2, 3)))(
        q, k, *weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, ANGLES)


# ------------------------------------------------- the layers that call it
@pytest.fixture(scope="module")
def trinity_reference():
    path = os.path.join(ROOT, "benchmark", "references", "trinity-mini.py")
    spec = importlib.util.spec_from_file_location("trinity_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("layer_type", ["sliding_attention",
                                        "full_attention"])
def test_trinitys_attention_matches_its_reference_through_the_kernels(
        interpreter, trinity_reference, layer_type):
    """``AfmoeAttention`` at the published head width (128: the stage takes
    its kernels; the core stays on XLA's route at this length) against the
    configuration's plain float32 reference, to the tolerance
    ``tests/test_trinity_model.py`` holds the XLA stage to: the QK-norms a
    head, RoPE where the layer has positions and nowhere else, query head h
    on key/value head h // 2."""
    from paddle_tpu.text.models import AfmoeAttention

    paddle.seed(5)
    layer = AfmoeAttention(64, layer_type, num_heads=4, num_kv_heads=2,
                           head_dim=128, sliding_window=96)
    rng = np.random.default_rng(5)
    for norm in (layer.q_norm, layer.k_norm):
        norm.weight.set_value(1 + rng.normal(0, 0.2, 128).astype(np.float32))
    x = rng.standard_normal((2, 512, 64)).astype(np.float32)
    before = {p: attention._QK_TOTAL.value(path=p) for p in ("kernel", "xla")}
    got = np.asarray(layer(paddle.to_tensor(x))._value)
    assert attention._QK_TOTAL.value(path="kernel") == before["kernel"] + 1
    assert attention._QK_TOTAL.value(path="xla") == before["xla"]
    w = {n: jnp.asarray(v) for n, v in layer.functional_state()[0].items()}
    sizes = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 128, "rms_norm_eps": 1e-5, "sliding_window": 96,
             "rope_theta": 10000.0}
    want = np.asarray(trinity_reference.attention(
        w, jnp.asarray(x), sizes, "", layer_type))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    other = ("full_attention" if layer_type == "sliding_attention"
             else "sliding_attention")
    off = np.asarray(trinity_reference.attention(
        w, jnp.asarray(x), sizes, "", other))
    assert np.abs(off - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("kind", ["afmoe", "gqa", "lfm2"])
def test_a_layer_takes_the_kernels_through_the_tape(kind):
    """The layers' own call, eagerly: Tensors in, ``backward`` through the
    tape, a row that is no whole block. With the interpreter flag Trinity's
    layer (heads of 128) and Qwen3-Next's (heads of 256, 64 features
    rotated, the query every second column block of its projection) count
    ``kernel``, without it ``xla``, and the flag's flip retraces (the
    stage's static arguments carry it); LFM2's (heads of 64) counts ``xla``
    either way. The two agree on the output and on every gradient."""
    from paddle_tpu.text.models import (AfmoeAttention, GatedGQAttention,
                                        Lfm2Attention)

    paddle.seed(6)
    layer = {"afmoe": lambda: AfmoeAttention(
                 32, "sliding_attention", num_heads=2, num_kv_heads=1,
                 head_dim=128, sliding_window=64),
             "gqa": lambda: GatedGQAttention(
                 32, num_heads=2, num_kv_heads=1, head_dim=256),
             "lfm2": lambda: Lfm2Attention(
                 128, num_heads=2, num_kv_heads=1)}[kind]()
    hidden = 128 if kind == "lfm2" else 32
    x = np.random.default_rng(6).standard_normal((2, 520, hidden)).astype(
        np.float32)
    results = {}
    for flag in (False, True, False):
        path = "kernel" if flag and kind != "lfm2" else "xla"
        other = "xla" if path == "kernel" else "kernel"
        before = {p: attention._QK_TOTAL.value(path=p) for p in (path, other)}
        paddle.set_flags({"pallas_interpret": flag})
        try:
            layer.clear_gradients()
            given = paddle.to_tensor(x, stop_gradient=False)
            out = layer(given)
            (out * out).sum().backward()
        finally:
            paddle.set_flags({"pallas_interpret": False})
        assert attention._QK_TOTAL.value(path=other) == before[other]
        assert attention._QK_TOTAL.value(path=path) > before[path]
        results.setdefault(flag, [out._value, given.grad._value] + [
            p.grad._value for p in layer.parameters()])
    assert len(results[True]) >= 8
    for a, b in zip(results[True], results[False]):
        close(a, b, 2e-5)
