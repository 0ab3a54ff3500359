"""``ops/placement.py``: the one answer to "Mosaic, interpreter or XLA, and
are the program's devices known". Its docstring's table as one parametrised
test (platform and device count patched, meshes from the conftest's CPU
devices), ``axis_size`` and ``on_mesh``'s optional seed, the expert layer's
call site (``sharded=False``: the grouped matmul has no shard_map), the
convolution stage's decision taken outside its dispatched op, the gated
short convolution's table (``shortconv_path``), the selective state-space
scan's (``ssd_path``: the table's ``sharded`` column at the granite cell's
widths) and the router's choice (``route_path``: the ``unsharded`` column,
as the grouped matmul beside it)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.incubate import moe
from paddle_tpu.ops import linear_attention, placement

M, I = "mosaic", "interpret"
#: (row, use_pallas_kernels, pallas_interpret, TPU, announced mesh's size
#: or None, devices) -> the answer a column: sharded=True, sharded=False,
#: no_fallback (the stream route's rule)
TABLE = [
    ("not-selected", False, True, True, 4, 1, (None, None, None)),
    ("interpreter", True, True, False, 4, 8, (I, I, I)),
    ("no-tpu", True, False, False, 1, 1, (None, None, None)),
    ("one-device", True, False, True, None, 1, (M, M, M)),
    ("plain-jit-many-devices", True, False, True, None, 8,
     (None, None, M)),
    ("mesh-of-one", True, False, True, 1, 8, (M, M, M)),
    ("mesh-of-many", True, False, True, 4, 8, (M, None, M)),
]
COLUMNS = {"sharded": dict(sharded=True), "unsharded": dict(sharded=False),
           "no_fallback": dict(sharded=True, no_fallback=True)}


@contextlib.contextmanager
def observed(monkeypatch, *, selected=True, interpret=False, tpu=True,
             mesh=None, devices=1):
    """What ``placement`` reads, set for the block: the two flags, the
    platform, the process's device count and the announced mesh (``mesh``
    devices on a dp axis, None = none announced)."""
    paddle.set_flags({"use_pallas_kernels": selected,
                      "pallas_interpret": interpret})
    monkeypatch.setattr(placement, "is_tpu_available", lambda: tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    announced = (contextlib.nullcontext() if mesh is None else
                 topology.tracing_for(topology.build_mesh(
                     dp=mesh, devices=jax.devices()[:mesh])))
    try:
        with announced:
            yield
    finally:
        paddle.set_flags({"use_pallas_kernels": True,
                          "pallas_interpret": False})


@pytest.mark.parametrize("column", list(COLUMNS))
@pytest.mark.parametrize(
    "selected, interpret, tpu, mesh, devices, answers",
    [row[1:] for row in TABLE], ids=[row[0] for row in TABLE])
def test_the_table(monkeypatch, selected, interpret, tpu, mesh, devices,
                   answers, column):
    with observed(monkeypatch, selected=selected, interpret=interpret,
                  tpu=tpu, mesh=mesh, devices=devices):
        assert placement.kernel(**COLUMNS[column]) == dict(
            zip(COLUMNS, answers))[column]


@pytest.mark.parametrize("axes, mp, ep", [(None, 1, 1), (dict(dp=4), 1, 1),
                                          (dict(dp=2, mp=2), 2, 1),
                                          (dict(ep=2, mp=4), 4, 2)])
def test_axis_size_is_the_announced_meshs(axes, mp, ep):
    """What ``on_mesh`` cuts the heads over: the announced mesh's 'mp',
    else 1; ``or_global`` falls back on the global mesh (all dp here)."""
    with (contextlib.nullcontext() if axes is None else topology.tracing_for(
            topology.build_mesh(**axes, devices=jax.devices()[:int(np.prod(
                list(axes.values())))]))):
        assert placement.axis_size("mp") == mp
        assert placement.axis_size("ep") == ep
        assert placement.axis_size("ep", or_global=True) == ep
        assert placement.axis_size("dp", or_global=True) == (
            jax.device_count() if axes is None else axes.get("dp", 1))


def test_on_mesh_passes_a_seed_only_where_one_is_given():
    """The scan and the convolution stage pass none: their kernels take the
    arrays alone, directly and inside the shard_map, whose operands are
    then the arrays and nothing else."""
    x = jnp.arange(8 * 4 * 6, dtype=jnp.float32).reshape(8, 4, 6)

    def plain(a, b):
        return a + b

    def seeded(a, b, seed):
        return a + b + seed.astype(a.dtype)

    seed = jnp.int32(3)
    np.testing.assert_array_equal(
        placement.on_mesh(plain, (x, x), head_axis=1), 2 * x)
    np.testing.assert_array_equal(
        placement.on_mesh(seeded, (x, x), head_axis=1, seed=seed), 2 * x + 3)
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    def on_mesh(call, **seed):
        with topology.tracing_for(mesh):
            return placement.on_mesh(call, (x, x), head_axis=1, **seed)

    for call, kwargs, want in ((plain, {}, 2 * x),
                               (seeded, dict(seed=seed), 2 * x + 3)):
        jaxpr = jax.make_jaxpr(lambda: on_mesh(call, **kwargs))()
        maps = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
        assert len(maps) == 1 and len(maps[0].invars) == 2 + len(kwargs)
        np.testing.assert_array_equal(
            jax.jit(lambda: on_mesh(call, **kwargs))(), want)
    # a seed of its own a shard: folded with the shard's place on each axis
    folded = jax.jit(lambda: on_mesh(seeded, seed=seed,
                                     seed_per_shard=True))() - 2 * x
    assert sorted(set(np.asarray(folded).ravel())) == [12, 13, 14, 15]


def _expert_layer(held):
    paddle.seed(3)
    return moe.MoELayer(128, 128, num_experts=8, top_k=2, held=held)


def _handed_kernels(monkeypatch):
    """Record the static ``kernel`` that ``_forward_sorted`` hands to the
    expert ops."""
    seen = []
    for name in ("_sorted_experts", "_held_experts"):
        real = getattr(moe, name)

        def spy(*args, _real=real, **static):
            seen.append(static["kernel"])
            return _real(*args, **static)

        monkeypatch.setattr(moe, name, spy)
    return seen


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["sorted", "held"])
@pytest.mark.parametrize("where, want", [
    ("mesh-of-many", None), ("mesh-of-one", "mosaic"),
    ("one-device", "mosaic"), ("plain-jit-many-devices", None),
    ("interpreter-on-a-mesh", "interpret")])
def test_the_expert_layer_asks_unsharded(monkeypatch, where, want, held):
    """``megablox.gmm`` has no shard_map: under an announced mesh of
    several devices (platform patched to a TPU) the sorted and the held
    path hand ``kernel=None`` to their ops and the traced layer holds a
    ``ragged_dot``; on a mesh of one device, or a one-device process, the
    Mosaic call; under the interpreter flag ``interpret`` on any mesh."""
    seen = _handed_kernels(monkeypatch)
    layer = _expert_layer(held)
    x = np.random.RandomState(0).randn(2, 64, 128).astype(np.float32)
    how = {"mesh-of-many": dict(mesh=4, devices=8),
           "mesh-of-one": dict(mesh=1, devices=8),
           "one-device": dict(devices=1),
           "plain-jit-many-devices": dict(devices=8),
           "interpreter-on-a-mesh": dict(interpret=True, tpu=False, mesh=4,
                                         devices=8)}[where]
    with observed(monkeypatch, **how):
        text = str(jax.make_jaxpr(
            lambda v: layer(paddle.Tensor(v))._value)(x))
    assert seen == [want]
    assert ("ragged_dot" in text) == (want is None)
    assert ("pallas_call" in text) == (want is not None)


def test_on_mesh_cuts_the_results_on_their_own_head_axis():
    """A stage that takes streams [B, T, heads x d] and leaves head arrays
    [B, heads, T, d] (``ops.attention.qk_heads``): ``head_axis`` cuts the
    operands, ``out_head_axis`` the results, and the values are the direct
    call's."""
    x = jnp.arange(4 * 6 * 8, dtype=jnp.float32).reshape(4, 6, 8)

    def split(a):
        return a.reshape(a.shape[0], 6, -1, 2).transpose(0, 2, 1, 3)

    want = split(x)
    mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])

    def on_mesh(a):
        with topology.tracing_for(mesh):
            return placement.on_mesh(split, (a,), head_axis=2,
                                     out_head_axis=1)

    np.testing.assert_array_equal(jax.jit(on_mesh)(x), want)
    text = str(jax.make_jaxpr(on_mesh)(x))
    assert "shard_map" in text


def test_the_convolution_stage_is_decided_outside_its_op(monkeypatch):
    """Two eager calls of a KimiDeltaAttention at a kernel-eligible shape
    (interpreter), ``use_pallas_kernels`` flipped between them: the answer
    rides ``kda_streams``' static arguments, so the second call is not
    served the first's cached trace — it counts ``path="xla"`` and runs the
    XLA stage."""
    from paddle_tpu.text.models import KimiDeltaAttention

    paddle.seed(11)
    layer = KimiDeltaAttention(64, num_heads=1, head_dim=128)
    x = paddle.to_tensor(
        np.random.RandomState(1).randn(1, 256, 64).astype(np.float32))
    ran = []
    for path in ("xla", "kernel"):
        real = getattr(linear_attention, "_conv_" + path)
        monkeypatch.setattr(
            linear_attention, "_conv_" + path,
            lambda *a, _real=real, _path=path: ran.append(_path) or _real(*a))

    def count(path):
        return linear_attention._CONV_TOTAL.value(path=path)

    paddle.set_flags({"pallas_interpret": True})
    try:
        before = count("kernel"), count("xla")
        first = layer(x)
        assert (count("kernel"), count("xla")) == (before[0] + 1, before[1])
        assert ran == ["kernel"]
        paddle.set_flags({"use_pallas_kernels": False})
        second = layer(x)
        assert (count("kernel"), count("xla")) == (before[0] + 1,
                                                   before[1] + 1)
        assert ran == ["kernel", "xla"]
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "use_pallas_kernels": True})
    np.testing.assert_allclose(np.asarray(first._value),
                               np.asarray(second._value), atol=2e-5)


#: (row, what ``placement`` observes, (seq, channels, taps, dtype)) -> the
#: gated short convolution's path; the LFM2 cell's stage is 8,192 tokens of
#: 2,048 channels a third, 3 taps, bf16
CELL = (8192, 2048, 3, jnp.bfloat16)
SHORTCONV = [
    ("the-cell-on-one-chip", dict(), CELL, "kernel"),
    ("the-check-in-float32", dict(), (8192, 2048, 3, jnp.float32), "kernel"),
    ("as-many-taps-as-rows-carried", dict(), (8192, 2048, 9, jnp.bfloat16),
     "kernel"),
    ("announced-mesh", dict(mesh=4, devices=8), CELL, "kernel"),
    ("interpreter", dict(interpret=True, tpu=False, devices=8), CELL,
     "kernel"),
    ("flag-off", dict(selected=False), CELL, "xla"),
    ("no-tpu", dict(tpu=False), CELL, "xla"),
    ("plain-jit-many-devices", dict(devices=8), CELL, "xla"),
    ("half-a-lane-group", dict(), (8192, 2112, 3, jnp.bfloat16), "xla"),
    ("taps-past-the-carried-rows", dict(), (8192, 2048, 10, jnp.bfloat16),
     "xla"),
    ("a-row-under-one-block", dict(), (255, 2048, 3, jnp.bfloat16), "xla"),
    ("a-third-too-wide-for-a-block", dict(), (8192, 2**16, 3, jnp.float32),
     "xla"),
    ("float16", dict(), (8192, 2048, 3, jnp.float16), "xla"),
]


@pytest.mark.parametrize("how, stage, path",
                         [row[1:] for row in SHORTCONV],
                         ids=[row[0] for row in SHORTCONV])
def test_the_gated_short_convolution_goes_by_what_it_observes(
        monkeypatch, how, stage, path):
    """``shortconv_path``: the kernels where ``placement`` lets a sharded
    site hold them and the shape fits (lane groups, the carried rows, a
    token block, bf16 or float32), the XLA stage everything else; one count
    a call under the label of the path taken."""
    before = {p: linear_attention._SHORTCONV_TOTAL.value(path=p)
              for p in ("kernel", "xla")}
    with observed(monkeypatch, **how):
        assert linear_attention.shortconv_path(*stage) == path
    for p, n in before.items():
        assert linear_attention._SHORTCONV_TOTAL.value(path=p) == n + (
            p == path), p


#: the granite cell's scan: 8,192 tokens, 64 heads of 64 in one group on a
#: state of 128, bf16 under amp O1
SCAN = (8192, 64, 1, 64, 128, jnp.bfloat16)
SSD = [
    ("the-cell-on-one-chip", dict(), SCAN, "kernel"),
    ("announced-mesh", dict(mesh=4, devices=8), SCAN, "kernel"),
    ("interpreter", dict(interpret=True, tpu=False, devices=8), SCAN,
     "kernel"),
    ("flag-off", dict(selected=False), SCAN, "chunked"),
    ("no-tpu", dict(tpu=False), SCAN, "chunked"),
    ("plain-jit-many-devices", dict(devices=8), SCAN, "chunked"),
    ("the-check-in-float32", dict(), SCAN[:5] + (jnp.float32,), "chunked"),
    ("eight-groups", dict(), (8192, 64, 8, 64, 128, jnp.bfloat16), "kernel"),
    ("values-of-128-on-a-state-of-256", dict(),
     (8192, 32, 1, 128, 256, jnp.bfloat16), "kernel"),
    ("a-row-under-one-block", dict(), (255,) + SCAN[1:], "chunked"),
    ("a-row-under-one-sub-block", dict(), (15,) + SCAN[1:], "recurrent"),
    ("half-a-lane-group-of-state", dict(),
     (8192, 64, 1, 64, 64, jnp.bfloat16), "chunked"),
    ("values-across-lane-groups", dict(),
     (8192, 64, 1, 96, 128, jnp.bfloat16), "chunked"),
    ("four-heads-a-group", dict(), (8192, 64, 16, 64, 128, jnp.bfloat16),
     "chunked"),
    ("the-length-alone", dict(), (8192,), "chunked"),
]


@pytest.mark.parametrize("how, scan, path", [row[1:] for row in SSD],
                         ids=[row[0] for row in SSD])
def test_the_state_space_scan_goes_by_what_it_observes(monkeypatch, how,
                                                       scan, path):
    """``ssd_path``: the Mosaic kernels where ``placement`` lets a sharded
    site hold them and the shape fits (a state of whole lane groups, values
    that divide or are a multiple of the 128 lanes, a head cut, bf16, a
    token block), the XLA scan everything else, the recurrence under 16
    tokens — no flag, argument or name of its own."""
    with observed(monkeypatch, **how):
        assert linear_attention.ssd_path(*scan) == path


#: the Qwen3-Next cell's router: 16,384 tokens choose 10 of 512 experts
ROUTER = (16384, 512, 10)
ROUTE = [
    ("the-cell-on-one-chip", dict(), ROUTER, "kernel"),
    ("nemotron-top-22", dict(), (4096, 512, 22), "kernel"),
    ("kimi-linear-256-experts", dict(), (16384, 256, 8), "kernel"),
    ("joyai-8192-tokens", dict(), (8192, 256, 8), "kernel"),
    ("trinity-128-experts", dict(), (16384, 128, 8), "kernel"),
    ("mesh-of-one", dict(mesh=1, devices=8), ROUTER, "kernel"),
    ("interpreter", dict(interpret=True, tpu=False, devices=8), ROUTER,
     "kernel"),
    # the expert layer takes no shard_map of its own: under dp4-style
    # meshes the XLA stage, as ragged_dot for the grouped matmul
    ("announced-mesh-of-many", dict(mesh=4, devices=8), ROUTER, "xla"),
    ("plain-jit-many-devices", dict(devices=8), ROUTER, "xla"),
    ("flag-off", dict(selected=False), ROUTER, "xla"),
    ("no-tpu", dict(tpu=False), ROUTER, "xla"),
    ("olmoe-64-experts", dict(), (16384, 64, 8), "kernel"),
    ("lfm2-32-experts", dict(), (32768, 32, 4), "xla"),
    ("tokens-under-one-tile", dict(), (511, 512, 10), "xla"),
    ("more-experts-than-a-tile-holds", dict(), (16384, 4096, 8), "xla"),
]


@pytest.mark.parametrize("how, router, path", [row[1:] for row in ROUTE],
                         ids=[row[0] for row in ROUTE])
def test_the_routers_choice_goes_by_what_it_observes(monkeypatch, how,
                                                     router, path):
    """``route_path``: the Mosaic stage where ``placement`` lets an
    UNSHARDED site hold it (no mesh of several devices), the tokens fill a
    tile and the experts are 64 or more and no more than a tile holds; the
    XLA stage everything else. ``route_kernel`` hands ``placement``'s answer
    on and counts one call under the label of the path taken."""
    before = {p: moe._ROUTE_TOTAL.value(path=p) for p in ("kernel", "xla")}
    with observed(monkeypatch, **how):
        assert moe.route_path(*router) == path
        handed = moe.route_kernel(*router)
        assert handed == (None if path == "xla" else
                          placement.kernel(sharded=False))
    assert (handed is None) == (path == "xla")
    for p, n in before.items():
        assert moe._ROUTE_TOTAL.value(path=p) == n + (p == path), p
