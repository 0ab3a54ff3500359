"""Sharded multi-chip serving (ISSUE 15): per-(bucket, mesh) pjit
programs behind the batching + decode engines.

The load-bearing contract (prototyped before the engines were touched,
like PR 12's): sharded-vs-single-chip replies are BITWISE identical
per wire dtype in the gemm regime when only output dims shard (the tp
discipline), and within the documented tolerance
(sharding.SHARDED_FLOAT_TOL) when a contraction dim shards (fsdp, or
tp feeding an attention contraction — XLA inserts a psum whose
reduction order differs). Decode solo-vs-batch determinism is bitwise
PER MESH regardless. Sharded engines need > 1 jax device, so every
sharded scenario runs in a subprocess (tests/sharded_worker.py) that
sets the device count before jax wakes up — or in a real
launch_collective pod over gloo CPU collectives (one device per
process, the PR 9 launcher).
"""
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.inference import sharding  # noqa: E402
from paddle_tpu.inference import wire_spec  # noqa: E402
from paddle_tpu.inference.server import (_encode_arrays,  # noqa: E402
                                         _decode_arrays, _read_all,
                                         serve_model)
from paddle_tpu.inference.sharding import ServingMesh  # noqa: E402
from paddle_tpu.jit import load as jit_load  # noqa: E402
from paddle_tpu.static import InputSpec  # noqa: E402

pytestmark = pytest.mark.sharded

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "sharded_worker.py")


def _save_mlp(tmp_path, name="m", mesh=None):
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m.eval()
    prefix = str(tmp_path / name)
    paddle.jit.save(m, prefix,
                    input_spec=[InputSpec([None, 8], "float32")],
                    mesh=mesh)
    return prefix


def _run_worker(mode, *args, env=None, timeout=600):
    e = dict(os.environ)
    e.pop("PADDLE_TPU_ARTIFACT_DIR", None)
    e.pop("PADDLE_TPU_SERVING_MESH", None)
    e.pop("PADDLE_TPU_SERVING_QUANT", None)
    if env:
        e.update(env)
    r = subprocess.run([sys.executable, WORKER, mode, *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=e)
    assert r.returncode == 0, f"worker {mode} failed:\n{r.stderr[-4000:]}"
    return r


# ---------------------------------------------------------------- descriptor
class TestDescriptor:
    def test_parse_canonical_roundtrip(self):
        assert ServingMesh.parse(None).descriptor == "single"
        assert ServingMesh.parse("single").descriptor == "single"
        assert ServingMesh.parse("").descriptor == "single"
        assert ServingMesh.parse("tp2").descriptor == "tp2"
        assert ServingMesh.parse("TP4").descriptor == "tp4"
        assert ServingMesh.parse("fsdp2").descriptor == "fsdp2"
        assert ServingMesh.parse("fsdp2xtp2").descriptor == "fsdp2xtp2"
        # the reference's model-parallel spelling normalizes to tp
        assert ServingMesh.parse("mp4").descriptor == "tp4"
        # pass-through + canonical is stable under re-parse
        m = ServingMesh.parse("fsdp2xtp4")
        assert ServingMesh.parse(m) is m
        assert ServingMesh.parse(m.descriptor) == m
        assert m.n_shards == 8 and not m.is_single

    @pytest.mark.parametrize("bad", ["bogus", "tp0", "tp", "fsdp0",
                                     "tp2xfsdp2", "dp2", "tp2x", "f32"])
    def test_invalid_descriptors_raise(self, bad):
        with pytest.raises(ValueError):
            ServingMesh.parse(bad)

    def test_env_resolution(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SERVING_MESH", raising=False)
        assert sharding.resolve(None).is_single
        monkeypatch.setenv("PADDLE_TPU_SERVING_MESH", "tp2")
        assert sharding.resolve(None).descriptor == "tp2"
        # explicit arg wins over env
        assert sharding.resolve("fsdp2").descriptor == "fsdp2"

    def test_param_spec_discipline(self):
        from jax.sharding import PartitionSpec as P

        m = ServingMesh.parse("fsdp2xtp2")
        assert m.param_spec((16, 32)) == P("sharding", "mp")
        assert m.param_spec((32,)) == P("mp")
        assert m.param_spec(()) == P()
        # indivisible dims stay replicated, per-dim
        assert m.param_spec((7, 32)) == P(None, "mp")
        assert m.param_spec((16, 9)) == P("sharding", None)
        assert m.param_spec((7, 9)) == P(None, None)
        # 3-D: first dim fsdp, last dim tp
        assert m.param_spec((4, 5, 8)) == P("sharding", None, "mp")
        tp = ServingMesh.parse("tp2")
        assert tp.param_spec((16, 32)) == P(None, "mp")
        assert tp.param_spec((17,)) == P()

    def test_shard_fraction_and_bytes(self):
        m = ServingMesh.parse("fsdp2xtp2")
        assert m.shard_fraction((16, 32)) == 0.25
        assert m.shard_fraction((32,)) == 0.5
        assert m.shard_fraction((7, 9)) == 1.0
        arrs = [np.zeros((16, 32), np.float32), np.zeros((7, 9),
                                                        np.float32)]
        # 16*32*4/4 + 7*9*4 (replicated)
        assert m.per_shard_bytes(arrs) == 16 * 32 + 7 * 9 * 4
        single = ServingMesh.parse(None)
        assert single.per_shard_bytes(arrs) == sum(a.nbytes for a in arrs)

    def test_check_nr_devices_gates_skew(self):
        class Fake:
            nr_devices = 4

        with pytest.raises(ValueError, match="mesh skew"):
            sharding.check_nr_devices(Fake(), None)
        sharding.check_nr_devices(Fake(), ServingMesh.parse("tp4"))
        with pytest.raises(ValueError, match="mesh skew"):
            sharding.check_nr_devices(Fake(), ServingMesh.parse("tp2"))

    def test_build_fails_fast_without_devices(self):
        # a mesh wider than the process's device count must raise
        # naming the remedy (the XLA device-count flag), never fail
        # mid-request
        import jax

        too_wide = f"tp{2 * len(jax.devices())}"
        with pytest.raises(ValueError, match="device"):
            ServingMesh.parse(too_wide).build()


# ----------------------------------------------------------- save/load stamp
class TestSaveRecordsMesh:
    def test_save_records_and_load_exposes(self, tmp_path):
        prefix = _save_mlp(tmp_path, mesh="mp2")
        meta = json.load(open(prefix + ".pdmeta.json"))
        # canonicalized at save time (mp2 -> tp2)
        assert meta["mesh"] == "tp2"
        assert jit_load(prefix)._serving_mesh == "tp2"

    def test_save_without_mesh_records_none(self, tmp_path):
        prefix = _save_mlp(tmp_path)
        meta = json.load(open(prefix + ".pdmeta.json"))
        assert meta["mesh"] is None
        assert jit_load(prefix)._serving_mesh is None

    def test_save_invalid_mesh_raises(self, tmp_path):
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 4))
        m.eval()
        with pytest.raises(ValueError):
            paddle.jit.save(m, str(tmp_path / "bad"),
                            input_spec=[InputSpec([None, 8], "float32")],
                            mesh="nope")


# ------------------------------------------------------------ fail-fast paths
class TestFailFast:
    def test_serve_model_typoed_mesh_fails_at_entry(self, tmp_path):
        # entry validation precedes the load: even a nonexistent prefix
        # gets the descriptor-grammar error, not a file error
        with pytest.raises(ValueError, match="descriptor"):
            serve_model(str(tmp_path / "nonexistent"), mesh="bogus")

    def test_serve_model_recorded_vs_declared_mismatch(self, tmp_path):
        prefix = _save_mlp(tmp_path, mesh="tp2")
        with pytest.raises(ValueError, match="serving mesh"):
            serve_model(prefix, dynamic_batching=True, mesh="single")

    def test_sharded_serving_requires_batching_engine(self, tmp_path):
        prefix = _save_mlp(tmp_path, mesh="tp2")
        with pytest.raises(ValueError, match="dynamic_batching"):
            serve_model(prefix)  # save's recorded mesh, no engine

    def test_engine_fails_fast_without_devices(self, tmp_path):
        import jax

        from paddle_tpu.inference.batching import BatchingEngine

        too_wide = f"tp{2 * len(jax.devices())}"
        prefix = _save_mlp(tmp_path)
        with pytest.raises(ValueError, match="device"):
            BatchingEngine.for_layer(jit_load(prefix), mesh=too_wide)

    def test_decode_engine_fails_fast_without_devices(self):
        import jax

        from decode_worker import toy_decode_model
        from paddle_tpu.inference.decode import DecodeEngine

        too_wide = f"tp{2 * len(jax.devices())}"
        with pytest.raises(ValueError, match="device"):
            DecodeEngine(toy_decode_model(hidden=8, vocab=16, seed=0),
                         mesh=too_wide, watchdog_interval=0)

    def test_hot_reload_cannot_flip_mesh(self, tmp_path):
        """A reload whose save records a DIFFERENT mesh than the one
        pinned at first load is refused — and the server keeps serving
        the old engine (the PR 5 reload-failure contract)."""
        prefix_a = _save_mlp(tmp_path, "a")  # no recorded mesh
        prefix_b = _save_mlp(tmp_path, "b", mesh="tp2")
        server = serve_model(prefix_a, dynamic_batching=True,
                             warmup=False, watchdog_interval=0)
        try:
            with pytest.raises(ValueError, match="serving mesh"):
                server.reload(prefix_b)
            # still serving the original single-chip engine
            x = np.ones((2, 8), np.float32)
            out = server._engine.infer([x], timeout=60)
            assert out[0].shape == (2, 4)
        finally:
            server.stop(drain=False)


# ----------------------------------------------- engine-level contract (4 dev)
class TestShardedContract:
    @pytest.fixture(scope="class")
    def contract(self, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("sharded") / "contract.json")
        store = str(tmp_path_factory.mktemp("sharded_store"))
        _run_worker("contract", out, "tp2", "fsdp2xtp2",
                    env={"SHARDED_WORKER_STORE": store})
        return json.load(open(out))

    def test_tp_mesh_is_bitwise_per_wire_dtype(self, contract):
        """The tentpole contract: output-dim-only sharding (tp) is
        BITWISE identical to single-chip for every wire dtype, at
        engine level, across coalesced and split-path requests."""
        d = contract["meshes"]["tp2"]["dtypes"]
        assert set(d) == {"f32", "i32", "i64", "bool"}
        for name, v in d.items():
            assert v["bitwise"], (name, v)
            assert v["stats_mesh"] == "tp2"

    def test_fsdp_mesh_within_documented_tolerance(self, contract):
        """Sharding a contraction dim makes XLA psum partial products:
        integer/bool dtypes stay exact, floats agree within
        SHARDED_FLOAT_TOL (the documented-tolerance arm)."""
        d = contract["meshes"]["fsdp2xtp2"]["dtypes"]
        for name in ("i32", "i64", "bool"):
            assert d[name]["bitwise"], d[name]
        assert d["f32"]["maxdiff"] <= sharding.SHARDED_FLOAT_TOL

    def test_ledger_events_mesh_tagged(self, contract):
        assert contract["meshes"]["tp2"]["ledger_mesh_tags"] == ["tp2"]
        assert contract["meshes"]["fsdp2xtp2"]["ledger_mesh_tags"] == \
            ["fsdp2xtp2"]
        # the sharding reached the HLO: the programs hold collectives
        for mesh in ("tp2", "fsdp2xtp2"):
            assert contract["meshes"][mesh]["ledger_collectives"] > 0

    def test_metrics_carry_mesh_const_label(self, contract):
        lines = contract["exposition_mesh_lines"]
        assert lines and all('mesh="tp2"' in line for line in lines)

    def test_sharded_store_roundtrip_zero_compiles(self, contract):
        """(bucket, mesh) artifacts persist: a fresh sharded engine
        rewarms entirely from the store (ZERO inline compiles) and
        replies bitwise-equal to the publisher."""
        st = contract["store"]
        assert st["publisher_compiles"] > 0
        assert st["rewarm_compiles"] == 0
        assert st["rewarm_loads"] == st["publisher_compiles"]
        assert st["rewarm_bitwise"]

    def test_mesh_skew_is_clean_store_miss(self, contract):
        """A single-chip engine against the sharded store: every
        lookup is a clean MISS (inline compiles, zero loads) and the
        replies are still bitwise-correct — never corruption."""
        st = contract["store"]
        assert st["skew_loads"] == 0
        assert st["skew_compiles"] > 0
        assert st["skew_bitwise_vs_single"]


# -------------------------------------------------------- decode (per mesh)
class TestShardedDecode:
    @pytest.fixture(scope="class")
    def record(self, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("sharded_dec") / "decode.json")
        store = str(tmp_path_factory.mktemp("sharded_dec_store"))
        _run_worker("decode", out, "tp2",
                    env={"SHARDED_WORKER_STORE": store})
        return json.load(open(out))

    def test_solo_vs_batch_bitwise_per_mesh(self, record):
        """The continuous-batching determinism contract holds UNDER
        the mesh: staggered in-batch sequences emit exactly their solo
        tokens (join/leave, mixed prompt lengths, i64 echo)."""
        assert record["solo_vs_batch_bitwise"]
        assert record["i64_echo"]
        assert record["stats_mesh"] == "tp2"

    def test_tokens_agree_with_single_chip(self, record):
        """Sharded logits sit within the documented tolerance of the
        single-chip ones; on this fixed toy the greedy argmax chain is
        the same, so the tokens agree exactly."""
        from decode_worker import reference_decode, toy_decode_model

        single = reference_decode(
            toy_decode_model(hidden=32, vocab=64, seed=0),
            np.array([3, 1, 4, 1, 5], np.int32), 12, max_seq_len=32)
        assert record["tokens"][0] == single.tolist()

    def test_decode_ladder_rewarms_from_store(self, record):
        st = record["store"]
        assert st["publisher_compiles"] > 0
        assert st["rewarm_compiles"] == 0
        assert st["rewarm_loads"] == st["publisher_compiles"]
        assert st["rewarm_bitwise"]


# ------------------------------------------------------------ wire level
class TestWireLevel:
    def _spawn_server(self, prefix, mesh, env=None):
        e = dict(os.environ)
        e.pop("PADDLE_TPU_ARTIFACT_DIR", None)
        e.pop("PADDLE_TPU_SERVING_MESH", None)
        e.pop("PADDLE_TPU_SERVING_QUANT", None)
        if env:
            e.update(env)
        proc = subprocess.Popen(
            [sys.executable, WORKER, "serve", prefix, mesh],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=e)
        line = proc.stdout.readline()
        if not line.startswith("PORT "):
            proc.kill()
            raise AssertionError(
                f"server failed: {line!r}\n{proc.stderr.read()[-2000:]}")
        return proc, int(line.split()[1])

    def _stop(self, proc, port):
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=5) as s:
                s.sendall(struct.pack("<IB", 1, wire_spec.CMD_STOP))
                _read_all(s, 5)
        except OSError:
            pass
        proc.wait(timeout=30)

    def _infer_bytes(self, port, x, timeout=120):
        body = wire_spec.build_request(wire_spec.CMD_INFER,
                                       _encode_arrays([x]))
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout) as s:
            s.settimeout(timeout)
            s.sendall(body)
            (blen,) = struct.unpack("<I", _read_all(s, 4))
            resp = _read_all(s, blen)
        assert resp[0] == wire_spec.STATUS_OK, resp[:1]
        return resp[1:]

    def _cmd_json(self, port, cmd):
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=30) as s:
            s.sendall(struct.pack("<IB", 1, cmd))
            (blen,) = struct.unpack("<I", _read_all(s, 4))
            resp = _read_all(s, blen)
        assert resp[0] == wire_spec.STATUS_OK
        return json.loads(resp[1:].decode())

    def test_wire_replies_bitwise_and_views_report_mesh(self, tmp_path):
        """Wire transparency: the sharded replica's cmd-1 reply BYTES
        equal the single-chip engine's for the same request (tp mesh,
        gemm regime), and cmd-3 health / cmd-5 stats name the mesh."""
        prefix = _save_mlp(tmp_path)
        # single-chip baseline: the same engine path, in-process
        from paddle_tpu.inference.batching import BatchingEngine

        eng = BatchingEngine.for_layer(jit_load(prefix), max_batch_size=4,
                                       watchdog_interval=0)
        eng.warmup()
        rng = np.random.RandomState(5)
        xs = [rng.randn(rows, 8).astype(np.float32) for rows in (2, 4, 3)]
        base_payloads = [_encode_arrays(eng.infer([x], timeout=60))
                         for x in xs]
        eng.close()

        proc, port = self._spawn_server(prefix, "tp2")
        try:
            for x, want in zip(xs, base_payloads):
                assert self._infer_bytes(port, x) == want
            health = self._cmd_json(port, wire_spec.CMD_HEALTH)
            assert health["engine"]["mesh"] == "tp2"
            stats = self._cmd_json(port, wire_spec.CMD_STATS)
            assert stats["mesh"] == "tp2"
        finally:
            self._stop(proc, port)

    def test_decode_stream_over_wire_matches_solo(self, tmp_path):
        """Streaming wire replies from a SHARDED decode replica:
        chunked tokens across a concurrent join equal the solo decode
        of the same prompts — the wire is mesh-invariant for decode
        too (cmd-5 stats reports the decode engine's mesh)."""
        env = {"SHARDED_WORKER_DECODE": "1",
               "DECODE_WORKER_MAX_SLOTS": "4",
               "DECODE_WORKER_MAX_SEQ": "32",
               "DECODE_WORKER_MAX_PROMPT": "8"}
        proc, port = self._spawn_server("unused", "tp2", env=env)

        def stream(prompt, max_new):
            from paddle_tpu.inference.server import _encode_decode_opts

            body = (struct.pack("<B", wire_spec.CMD_INFER)
                    + _encode_arrays([prompt])
                    + _encode_decode_opts(max_new))
            chunks = []
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=120) as s:
                s.settimeout(240)
                s.sendall(struct.pack("<I", len(body)) + body)
                while True:
                    (blen,) = struct.unpack("<I", _read_all(s, 4))
                    resp = _read_all(s, blen)
                    if len(resp) > 1 and resp[0] in (
                            wire_spec.STATUS_OK, wire_spec.STATUS_STREAM):
                        arrs = _decode_arrays(resp[1:])
                        if arrs and arrs[0].size:
                            chunks.append(arrs[0])
                    if resp[0] != wire_spec.STATUS_STREAM:
                        assert resp[0] == wire_spec.STATUS_OK
                        return np.concatenate(chunks) if chunks else \
                            np.zeros((0,), prompt.dtype)

        try:
            prompt = np.array([3, 1, 4, 1, 5], np.int32)
            short = np.array([2, 7], np.int32)
            solo_main = stream(prompt, 10)
            solo_short = stream(short, 5)
            # concurrent joins must not perturb either stream
            import threading

            got = {}

            def one(key, p, n):
                got[key] = stream(p, n)

            ts = [threading.Thread(target=one, args=(i, p, n))
                  for i, (p, n) in enumerate(
                      [(prompt, 10), (short, 5), (prompt, 10)])]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert np.array_equal(got[0], solo_main)
            assert np.array_equal(got[2], solo_main)
            assert np.array_equal(got[1], solo_short)
            stats = self._cmd_json(port, wire_spec.CMD_STATS)
            assert stats["decode"]["mesh"] == "tp2"
        finally:
            self._stop(proc, port)


# ------------------------------------------------- multi-process (gloo) mesh
class TestMultiProcessMesh:
    def test_cross_process_tp2_bitwise_vs_single(self, tmp_path):
        """A REAL cross-process serving mesh: tp2 spanning two
        single-device processes over gloo CPU collectives (the PR 9
        launcher). Every rank runs the identical lockstep request
        sequence; rank 0's replies must be bitwise-equal to the
        single-chip engine's."""
        import hashlib

        from paddle_tpu.distributed import launch_mod
        from paddle_tpu.inference.batching import BatchingEngine

        prefix = _save_mlp(tmp_path)
        eng = BatchingEngine.for_layer(jit_load(prefix), max_batch_size=4,
                                       watchdog_interval=0)
        eng.warmup()
        rng = np.random.RandomState(3)
        shas = []
        for rows in (2, 3, 4):
            x = rng.randn(rows, 8).astype(np.float32)
            shas.append(hashlib.sha256(
                eng.infer([x], timeout=60)[0].tobytes()).hexdigest())
        eng.close()

        outdir = tmp_path / "out"
        outdir.mkdir()
        env_prev = os.environ.get("SHARDED_WORKER_PREFIX")
        os.environ["SHARDED_WORKER_PREFIX"] = prefix
        try:
            launch_mod.launch_collective(
                WORKER, ["rank", str(outdir), "tp2"], nproc_per_node=2,
                log_dir=str(tmp_path / "logs"), transient_retries=2)
        finally:
            if env_prev is None:
                os.environ.pop("SHARDED_WORKER_PREFIX", None)
            else:
                os.environ["SHARDED_WORKER_PREFIX"] = env_prev
        rec = json.load(open(outdir / "rank0.json"))
        assert rec["world"] == 2
        assert rec["mesh"] == "tp2"
        assert rec["shas"] == shas
