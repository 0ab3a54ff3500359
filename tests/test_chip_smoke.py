"""chip_smoke.py off the chip, and the no-silent-fallback rules it rests on.

The chip run itself happens through the chip tool (README "Running");
here the CPU rehearsal (``--dry-run``: toy widths, Pallas interpreter)
must pass phase by phase, the default invocation must refuse to run
without a TPU, and the fallbacks that would hide a missing device or a
broken export must raise.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import errors
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, devices=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    return r, lines


@pytest.fixture(scope="module")
def dry_run():
    r, lines = _run_smoke("--dry-run")
    assert len(lines) >= 3, r.stdout + r.stderr[-3000:]
    return r, lines


class TestDryRun:
    def test_header_labels_itself(self, dry_run):
        _, lines = dry_run
        head = lines[0]
        assert head["dry_run"] is True and head["platform"] == "cpu"
        assert head["jax"] == jax.__version__
        assert {"jaxlib", "libtpu", "kind", "count", "compile_cache_dir",
                "compile_cache_entries"} <= set(head)

    @pytest.mark.parametrize("phase,name", [
        (0, "run_check"), (1, "train_bert"), (2, "serve_bert"),
        (3, "flash_kernels"), (4, "decode_engine_toy")])
    def test_phase_ok(self, dry_run, phase, name):
        r, lines = dry_run
        rec = next(l for l in lines if l.get("phase") == phase)
        assert rec["name"] == name
        assert rec["ok"] is True, rec.get("error", "") + r.stderr[-3000:]
        # smoke observations are named so, never as a metric
        assert {"smoke_wall_s", "smoke_compile_s", "smoke_rest_s"} <= set(rec)

    def test_phase_contents(self, dry_run):
        _, lines = dry_run
        by = {l["phase"]: l for l in lines if "phase" in l}
        assert by[1]["steps"] >= 13 and by[1]["loss_last"] < by[1]["loss_first"]
        assert by[2]["polymorphic"] is True
        assert by[2]["single"]["traffic_compiles"] == 0
        assert by[2]["single"]["warmup_compiles"] == len(
            by[2]["single"]["buckets"])
        # the interpreter was asked for: no Mosaic call may be lowered
        assert all(v == 0 for shape in by[3].values() if isinstance(shape, dict)
                   for k, v in shape.items() if k.startswith("mosaic_calls"))
        assert by[4]["post_warmup_compiles"] == 0

    def test_last_line_is_the_result(self, dry_run):
        r, lines = dry_run
        assert r.returncode == 0, r.stderr[-3000:]
        assert lines[-1] == {"ok": True, "dry_run": True, "device": {
            "platform": "cpu", "kind": lines[0]["kind"], "count": 1}}

    @pytest.mark.slow  # the N > 1 arms: tp<N> serving, dp=N, hybrid axes
    def test_four_devices_run_phase_5(self):
        r, lines = _run_smoke("--dry-run", devices=4, timeout=1200)
        assert r.returncode == 0, r.stdout + r.stderr[-3000:]
        by = {l["phase"]: l for l in lines if "phase" in l}
        assert by[5]["ok"] and "tp4" in by[2] and by[1]["mesh"] == "dp4"


def test_without_a_tpu_the_default_invocation_runs_nothing():
    r, lines = _run_smoke()
    assert r.returncode == 2
    assert lines == [], r.stdout  # no header, no phase, no result
    assert "no TPU" in r.stderr and "Traceback" not in r.stderr


# ------------------------------------------------ no silent fallbacks

def test_tpuplace_without_a_tpu_raises():
    with pytest.raises(errors.UnavailableError, match="no 'tpu' device"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.CPUPlace().jax_device().platform == "cpu"


def test_jit_save_of_an_unexportable_layer_raises(tmp_path):
    class HostBranch(nn.Layer):
        def forward(self, x):
            # a host read of a traced value: no export can contain it
            return x if float(x.sum().numpy()) > 0 else -x

    prefix = str(tmp_path / "m")
    with pytest.raises(RuntimeError, match="does not export"):
        paddle.jit.save(HostBranch(), prefix,
                        input_spec=[InputSpec([None, 4], "float32")])
    assert not os.path.exists(prefix + ".pdmeta.json")  # no params-only


def test_bert_saves_batch_polymorphic(tmp_path):
    """BertEmbeddings expands position ids to [batch, seq]: the shape
    list it builds has to carry the symbolic batch dim, or the export
    fails (and used to be pinned to batch 1 behind the caller's back)."""
    from paddle_tpu.text.models import BertModel

    m = BertModel(vocab_size=64, hidden_size=16, num_hidden_layers=1,
                  num_attention_heads=2, intermediate_size=32,
                  max_position_embeddings=16)
    m.eval()
    prefix = str(tmp_path / "bert")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([None, 8], "int32")])
    with open(prefix + ".pdmeta.json") as f:
        meta = json.load(f)
    assert meta["format"] == "stablehlo" and meta["polymorphic"] is True
    ids = np.random.RandomState(0).randint(0, 64, (3, 8)).astype(np.int32)
    seq, _ = paddle.jit.load(prefix)(paddle.to_tensor(ids))
    np.testing.assert_allclose(seq.numpy(), m(paddle.to_tensor(ids))[0].numpy(),
                               rtol=1e-5, atol=1e-5)


def test_benchmark_has_no_peaks_for_an_unknown_device():
    sys.path.insert(0, REPO)
    try:
        from benchmark.harness import peaks
    finally:
        sys.path.pop(0)
    assert "TPU v5 lite" in peaks.PEAKS
    with pytest.raises(KeyError, match="peaks table"):
        peaks.peaks_for(jax.devices()[0].device_kind)  # the tests' CPU


# -------------------------------------------------- compile cache rule

@pytest.fixture
def config_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_cache_dir_from_outside_is_not_set_in_code(monkeypatch, tmp_path,
                                                   config_updates):
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    (tmp_path / "torn-cache").write_bytes(b"")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 1.0
    assert not (tmp_path / "torn-cache").exists()  # zero-byte entry scrubbed


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_compile_cache")
    assert configure_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want


# ------------------------------------- the kernel under a device mesh

def test_flash_kernel_shards_itself_over_the_traced_mesh():
    """GSPMD cannot partition a Mosaic kernel: inside a step traced for a
    multi-device mesh the kernel runs under a shard_map (batch over the
    data axes, heads over mp) and must equal the unsharded result."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core import random as random_core
    from paddle_tpu.distributed import topology
    from paddle_tpu.ops import attention

    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 128})
    try:
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(4, 2, 128, 16), jnp.float32)
                   for _ in range(3))

        def loss(q, k, v, p=0.0):
            out = attention.scaled_dot_product_attention(
                paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
                is_causal=True, dropout_p=p, training=True)._value
            return jnp.sum(jnp.sin(out)), out

        grad = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)
        g_ref, o_ref = jax.jit(grad)(q, k, v)
        mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        sh = NamedSharding(mesh, P("dp"))

        def on_mesh(q, k, v):
            with topology.tracing_for(mesh):
                return grad(q, k, v)

        g, o = jax.jit(on_mesh, in_shardings=(sh, sh, sh))(q, k, v)
        assert o.sharding.spec == P("dp", "mp")
        np.testing.assert_allclose(o, o_ref, rtol=1e-6, atol=1e-6)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

        def dropped(q, k, v):
            with topology.tracing_for(mesh), \
                    random_core.rng_guard(jax.random.PRNGKey(3)):
                return loss(q, k, v, 0.5)[1]

        # every shard counts (batch, head) from 0: without a per-shard
        # seed all four would drop the same entries
        od = jax.jit(dropped, in_shardings=(sh, sh, sh))(
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.ones_like(v))
        blocks = [np.asarray(od[b:b + 2, h:h + 1])
                  for b in (0, 2) for h in (0, 1)]
        assert not any(np.array_equal(blocks[0], x) for x in blocks[1:])
    finally:
        paddle.set_flags({"pallas_interpret": False,
                          "pallas_attention_min_seq": 1024})


def test_short_kernel_shards_its_batch_over_the_traced_mesh():
    """The whole-sequence kernel goes through the same shard_map wrapper:
    the packed batch over the data axes (an 'mp' axis computes it whole),
    results — dropout masks too — equal to the unsharded run."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core import random as random_core
    from paddle_tpu.distributed import topology
    from paddle_tpu.ops import attention

    paddle.set_flags({"pallas_interpret": True})
    try:
        rng = np.random.RandomState(0)
        qkv = jnp.asarray(rng.randn(4, 128, 3 * 128), jnp.float32)

        def loss(qkv, p=0.0):
            out = attention.packed_self_attention(
                paddle.Tensor(qkv), 2, dropout_p=p, training=True)._value
            return jnp.sum(jnp.sin(out)), out

        grad = jax.grad(loss, has_aux=True)
        g_ref, o_ref = jax.jit(grad)(qkv)
        mesh = topology.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
        sh = NamedSharding(mesh, P("dp"))

        def on_mesh(qkv):
            with topology.tracing_for(mesh):
                return grad(qkv)

        before = attention._ROUTE_TOTAL.value(route="short")
        g, o = jax.jit(on_mesh, in_shardings=(sh,))(qkv)
        assert attention._ROUTE_TOTAL.value(route="short") == before + 1
        assert o.sharding.spec == P("dp")
        np.testing.assert_allclose(o, o_ref, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-6)

        def dropped(qkv):
            with topology.tracing_for(mesh), \
                    random_core.rng_guard(jax.random.PRNGKey(3)):
                return loss(qkv, 0.5)[1]

        # q = k = 0, v = 1: the output is the kept share of each row
        ones = jnp.concatenate([jnp.zeros((4, 128, 256), jnp.float32),
                                jnp.ones((4, 128, 128), jnp.float32)], -1)
        od = np.asarray(jax.jit(dropped, in_shardings=(sh,))(ones))
        assert not np.array_equal(od[:2], od[2:])
        # the mask hashes the rows' numbers in the whole batch: the mesh
        # does not change it
        with random_core.rng_guard(jax.random.PRNGKey(3)):
            whole = np.asarray(jax.jit(lambda x: loss(x, 0.5)[1])(ones))
        assert np.array_equal(od, whole)
    finally:
        paddle.set_flags({"pallas_interpret": False})


@pytest.mark.parametrize("builder", ["train_step", "fsdp", "localsgd"])
def test_step_builders_announce_their_mesh(builder, monkeypatch):
    """The gate picks the short kernel only where it knows the program's
    devices, so every builder that owns a mesh says so (tracing_for). The
    kernel then sees one shard's rows with every mesh axis manual around
    it: under on_mesh's shard_map, or LocalSGD's own."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import comm_opt, spmd, topology
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import flash_attention

    seen = []
    real = flash_attention.mha_packed

    def spy(qkv, *args, **kwargs):
        seen.append((topology.traced_mesh(), qkv.shape[0], set(
            jax.sharding.get_abstract_mesh().manual_axes)))
        return real(qkv, *args, **kwargs)

    monkeypatch.setattr(flash_attention, "mha_packed", spy)
    paddle.set_flags({"pallas_interpret": True})
    try:
        paddle.seed(0)
        mesh = topology.build_mesh(dp=4, devices=jax.devices()[:4])
        blocks = [nn.TransformerEncoderLayer(128, 2, 128, dropout=0.1)
                  for _ in range(2)]
        model = nn.Sequential(*blocks)
        opt = optimizer.SGD(0.01, parameters=model.parameters())
        build = {"train_step": spmd.build_train_step,
                 "fsdp": spmd.build_fsdp_train_step,
                 "localsgd": comm_opt.build_localsgd_train_step}[builder]
        step, init = build(model, lambda o, t: jnp.mean((o - t) ** 2), opt,
                           mesh=mesh)
        x = np.random.RandomState(0).randn(8, 128, 128).astype(np.float32)
        before = attention._ROUTE_TOTAL.value(route="short")
        out = step(*init(), x, x)
        assert np.isfinite(float(out[0]))
        assert attention._ROUTE_TOTAL.value(route="short") > before
        assert seen and all(
            m is mesh and rows == 2 and manual == set(mesh.axis_names)
            for m, rows, manual in seen), seen
    finally:
        paddle.set_flags({"pallas_interpret": False})
