"""Subprocess / multi-process worker for the sharded-serving tests
(tests/test_sharded_serving.py, tests/test_fleet.py).

Sharded engines need more than one jax device; the tier-1 parent
process initialized jax with one CPU device, so every sharded scenario
runs HERE — a fresh process that sets
``--xla_force_host_platform_device_count`` before jax wakes up
(single-process multi-device), or a rank of a
``launch_collective`` pod (one device per process, a real
cross-process mesh over gloo CPU collectives — the PR 9 launcher).

Modes (argv[1]):

  contract <outfile> <mesh> [mesh...]
      Single-process, SHARDED_WORKER_DEVICES virtual devices. Per wire
      dtype (f32/i32/i64/bool) build the toy model, run the SAME
      requests through a single-chip engine and each sharded engine,
      and dump bitwise/maxdiff verdicts + engine stats + ledger mesh
      tags + the metrics exposition. With SHARDED_WORKER_STORE set,
      also prove the (bucket, mesh) store round trip: a publisher
      warms + publishes, a fresh engine rewarms with zero inline
      compiles, replies bitwise-equal; a single-chip engine against
      the same store cleanly misses (mesh skew is a key miss, never
      corruption).

  decode <outfile> <mesh>
      Single-process multi-device. The decode determinism contract PER
      MESH: staggered concurrent sequences (join/leave, seq-bucket
      climb, i64 echo) must each emit EXACTLY their solo tokens under
      the same mesh; plus a fresh-engine store rewarm with zero inline
      compiles when SHARDED_WORKER_STORE is set.

  serve <prefix> <mesh>
      Single-process multi-device serve_model replica (prints
      ``PORT <n>``); the wire-level equivalence and fleet-relay
      tests drive it. SHARDED_WORKER_DECODE=1 serves
      the toy decode model through a DecodeEngine instead.

  rank <outdir> <mesh>
      One rank of a launch_collective pod (gloo CPU collectives, one
      device per process): init_parallel_env, build the cross-process
      serving mesh, warm a sharded BatchingEngine, run the fixed
      request sequence in lockstep, rank 0 dumps outputs + stats.
"""
import json
import os
import sys


def _setup_devices():
    n = int(os.environ.get("SHARDED_WORKER_DEVICES", "4"))
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _sha(arr):
    import hashlib

    return hashlib.sha256(arr.tobytes()).hexdigest()


# ------------------------------------------------------------- toy models
def build_models():
    """One jit-saved toy model per wire dtype (mirrors the artifact
    suite's dtype matrix): f32 exercises the sharded gemms, the
    int/bool models prove integer bytes survive a sharded program
    byte-for-byte."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, 16)
            self.fc2 = nn.Linear(16, 4)

        def forward(self, x):
            return self.fc2(nn.functional.relu(self.fc1(x)))

    class IntOps(nn.Layer):
        def forward(self, x):
            return x * 3 + 1

    class BoolOps(nn.Layer):
        def forward(self, x):
            return paddle.logical_not(x)

    root = tempfile.mkdtemp(  # tpu-lint: disable=TPU506  # session-lifetime model dir, reaped with the tmpfs
        prefix="sharded_models_")
    out = {}
    for name, cls, dtype in (("f32", MLP, "float32"),
                             ("i32", IntOps, "int32"),
                             ("i64", IntOps, "int64"),
                             ("bool", BoolOps, "bool")):
        paddle.seed(0)
        m = cls()
        m.eval()
        prefix = os.path.join(root, f"m-{name}")
        paddle.jit.save(m, prefix,
                        input_spec=[InputSpec([None, 8], dtype)])
        out[name] = prefix
    return out


def _gen(name, rng, rows):
    import numpy as np

    if name == "f32":
        return rng.randn(rows, 8).astype(np.float32)
    if name == "i32":
        return rng.randint(-9, 9, (rows, 8)).astype(np.int32)
    if name == "i64":
        return rng.randint(-9, 9, (rows, 8)).astype(np.int64)
    return rng.rand(rows, 8) > 0.5


# ----------------------------------------------------------------- contract
def run_contract(outfile, meshes):
    import numpy as np
    from paddle_tpu.inference.batching import BatchingEngine
    from paddle_tpu.jit import load as jit_load
    from paddle_tpu.obs import metrics as obs_metrics
    from paddle_tpu.obs import prometheus as obs_prometheus
    from paddle_tpu.obs.ledger import LEDGER

    prefixes = build_models()
    rng = np.random.RandomState(3)
    # rows 2/3 coalesce in the gemm regime, 5 exercises the split path
    # (4 + a min_bucket-2 tail). Rows stay >= 2 on purpose: bucket 1 is
    # XLA's gemv regime, whose kernel differs per weight-shard width —
    # the PR 4 one-row float carve-out applies PER MESH (documented in
    # README "Sharded serving"), so the bitwise matrix is the gemm
    # regime's
    inputs = {name: [_gen(name, rng, rows) for rows in (2, 3, 5)]
              for name in prefixes}

    def run_all(name, mesh, tag):
        eng = BatchingEngine.for_layer(jit_load(prefixes[name]),
                                       max_batch_size=4,
                                       watchdog_interval=0,
                                       mesh=mesh, name=tag)
        eng.warmup()
        outs = [eng.infer([x], timeout=120) for x in inputs[name]]
        stats = eng.stats()
        eng.close()
        return outs, stats

    record = {"meshes": {}, "dtypes": sorted(prefixes)}
    singles = {}
    for name in prefixes:
        singles[name], _ = run_all(name, None, f"single-{name}")
    for mesh in meshes:
        LEDGER.reset()
        per_dtype = {}
        for name in prefixes:
            outs, stats = run_all(name, mesh, f"{mesh}-{name}")
            per_dtype[name] = {
                "bitwise": all(
                    a[0].dtype == b[0].dtype
                    and a[0].tobytes() == b[0].tobytes()
                    for a, b in zip(singles[name], outs)),
                "maxdiff": max(
                    float(np.max(np.abs(
                        np.asarray(a[0], np.float64)
                        - np.asarray(b[0], np.float64))))
                    for a, b in zip(singles[name], outs)),
                "stats_mesh": stats["mesh"],
                "compiles": stats["compiles"],
            }
        events = LEDGER.events("serving/")
        record["meshes"][mesh] = {
            "dtypes": per_dtype,
            "ledger_mesh_tags": sorted({e.get("mesh") for e in events}),
            "ledger_collectives": sum(
                e.get("op_counts", {}).get(op, 0) for e in events
                for op in ("all-gather", "all-reduce")),
        }
    # metrics label check: render while a sharded engine is LIVE (its
    # registry collector unregisters on close)
    probe = BatchingEngine.for_layer(jit_load(prefixes["f32"]),
                                     max_batch_size=4,
                                     watchdog_interval=0,
                                     mesh=meshes[0], name="mesh-probe")
    try:
        probe.warmup()
        text = obs_prometheus.render(obs_metrics.REGISTRY)
    finally:
        probe.close()
    record["exposition_mesh_lines"] = [
        line for line in text.splitlines()
        if line.startswith("paddle_serving_compiles_total")
        and 'engine="mesh-probe"' in line][:8]

    # ------------------------------------------------ store round trip
    store_dir = os.environ.get("SHARDED_WORKER_STORE")
    if store_dir:
        os.environ["PADDLE_TPU_ARTIFACT_DIR"] = store_dir
        mesh = meshes[0]
        name = "f32"
        pub_outs, pub_stats = run_all(name, mesh, "store-pub")
        warm_outs, warm_stats = run_all(name, mesh, "store-warm")
        skew_outs, skew_stats = run_all(name, None, "store-skew")
        record["store"] = {
            "mesh": mesh,
            "publisher_compiles": pub_stats["compiles"],
            "publisher_loads": pub_stats["store_loads"],
            "rewarm_compiles": warm_stats["compiles"],
            "rewarm_loads": warm_stats["store_loads"],
            "rewarm_bitwise": all(
                a[0].tobytes() == b[0].tobytes()
                for a, b in zip(pub_outs, warm_outs)),
            # a single-chip engine against the sharded store: mesh
            # skew must be a clean MISS (inline compiles, zero loads,
            # correct replies) in this direction too
            "skew_compiles": skew_stats["compiles"],
            "skew_loads": skew_stats["store_loads"],
            "skew_bitwise_vs_single": all(
                a[0].tobytes() == b[0].tobytes()
                for a, b in zip(singles[name], skew_outs)),
        }
        os.environ.pop("PADDLE_TPU_ARTIFACT_DIR")

    with open(outfile + ".tmp", "w") as f:
        json.dump(record, f)
    os.replace(outfile + ".tmp", outfile)


# ------------------------------------------------------------------- decode
def run_decode(outfile, mesh):
    import threading

    import numpy as np
    from decode_worker import toy_decode_model
    from paddle_tpu.inference.decode import DecodeEngine

    def solo(prompt, n):
        m = toy_decode_model(hidden=32, vocab=64, seed=0)
        eng = DecodeEngine(m, max_slots=1, max_seq_len=32,
                           min_seq_bucket=8, watchdog_interval=0,
                           mesh=mesh, name="sharded-solo")
        try:
            return eng.generate(prompt, max_new_tokens=n, timeout=240)
        finally:
            eng.close()

    main_prompt = np.array([3, 1, 4, 1, 5], np.int32)
    short64 = np.array([2, 7], np.int64)
    solo_main = solo(main_prompt, 12)
    solo_short = solo(short64, 6)

    m = toy_decode_model(hidden=32, vocab=64, seed=0)
    eng = DecodeEngine(m, max_slots=4, max_seq_len=32, min_seq_bucket=8,
                       watchdog_interval=0, mesh=mesh,
                       name="sharded-batch")
    results = [None] * 4
    plan = [(main_prompt, 12, 0.0), (short64, 6, 0.02),
            (main_prompt, 12, 0.05), (short64, 6, 0.08)]

    def one(i, prompt, n, delay):
        import time

        time.sleep(delay)
        results[i] = eng.submit(prompt, max_new_tokens=n).result(240)

    threads = [threading.Thread(target=one, args=(i, *p))
               for i, p in enumerate(plan)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = eng.stats()
    eng.close()

    record = {
        "mesh": mesh,
        "stats_mesh": stats["mesh"],
        # the load-bearing streaming contract: in-batch == solo,
        # bitwise, across staggered join/leave — and the i64 prompt's
        # tokens echo in i64
        "solo_vs_batch_bitwise": (
            np.array_equal(solo_main, results[0])
            and np.array_equal(solo_main, results[2])
            and np.array_equal(solo_short, results[1])
            and np.array_equal(solo_short, results[3])),
        "i64_echo": str(results[1].dtype) == "int64",
        "tokens": [np.asarray(r).tolist() for r in results],
    }

    store_dir = os.environ.get("SHARDED_WORKER_STORE")
    if store_dir:
        os.environ["PADDLE_TPU_ARTIFACT_DIR"] = store_dir
        m2 = toy_decode_model(hidden=32, vocab=64, seed=0)
        # pinned model identity: the lazy fingerprint hashes the step
        # export, whose serialized bytes embed trace-time source
        # locations — stable across processes running the SAME code
        # path (how real replicas share a ladder) but not across two
        # differently-lined call sites in one test. The key's mesh
        # field still separates sharded/single identities.
        m2._fingerprint = "toy-decode-sharded-test"
        pub = DecodeEngine(m2, max_slots=4, max_seq_len=32,
                           min_seq_bucket=8, watchdog_interval=0,
                           mesh=mesh, name="sharded-pub")
        pub.warmup()
        pub_stats = pub.stats()
        pub.close()
        m3 = toy_decode_model(hidden=32, vocab=64, seed=0)
        m3._fingerprint = "toy-decode-sharded-test"
        warm = DecodeEngine(m3, max_slots=4, max_seq_len=32,
                            min_seq_bucket=8, watchdog_interval=0,
                            mesh=mesh, name="sharded-rewarm")
        warm.warmup()
        warm_tokens = warm.generate(main_prompt, max_new_tokens=12,
                                    timeout=240)
        warm_stats = warm.stats()
        warm.close()
        record["store"] = {
            "publisher_compiles": pub_stats["compiles"],
            "rewarm_compiles": warm_stats["compiles"],
            "rewarm_loads": warm_stats["store_loads"],
            "rewarm_bitwise": bool(np.array_equal(solo_main,
                                                  warm_tokens)),
        }
        os.environ.pop("PADDLE_TPU_ARTIFACT_DIR")

    with open(outfile + ".tmp", "w") as f:
        json.dump(record, f)
    os.replace(outfile + ".tmp", outfile)


# -------------------------------------------------------------------- serve
def run_serve(prefix, mesh):
    from paddle_tpu.inference.server import PredictorServer, serve_model

    if os.environ.get("SHARDED_WORKER_DECODE") == "1":
        from decode_worker import toy_decode_model
        from paddle_tpu.inference.decode import DecodeEngine

        model = toy_decode_model(hidden=32, vocab=64, seed=0)
        engine = DecodeEngine(
            model, mesh=mesh,
            max_slots=int(os.environ.get("DECODE_WORKER_MAX_SLOTS", "8")),
            max_seq_len=int(os.environ.get("DECODE_WORKER_MAX_SEQ", "64")),
            max_prompt_len=int(os.environ.get("DECODE_WORKER_MAX_PROMPT",
                                              "16")),
            max_queue=256)
        engine.warmup()
        server = PredictorServer(lambda *a: list(a),
                                 decode_engine=engine,
                                 own_decode_engine=True)
    else:
        server = serve_model(prefix, dynamic_batching=True,
                             max_batch_size=4, mesh=mesh,
                             watchdog_interval=0)
    print(f"PORT {server.port}", flush=True)
    try:
        server._thread.join()
    except KeyboardInterrupt:
        pass
    server.stop()


# --------------------------------------------------------------------- rank
def run_rank(outdir, mesh):
    """One rank of a real cross-process serving mesh: gloo CPU
    collectives carry the sharded matmuls, every rank runs the
    IDENTICAL request sequence in lockstep (submit-then-wait, one
    group per request — same program order on every rank, which is
    all blocking collectives need)."""
    import numpy as np
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.batching import BatchingEngine
    from paddle_tpu.jit import load as jit_load

    dist.init_parallel_env()
    rank = dist.get_rank()
    prefix = os.environ["SHARDED_WORKER_PREFIX"]

    layer = jit_load(prefix)
    engine = BatchingEngine.for_layer(layer, max_batch_size=4,
                                      watchdog_interval=0, mesh=mesh,
                                      name=f"rank{rank}")
    engine.warmup()
    rng = np.random.RandomState(3)
    outs = []
    for rows in (2, 3, 4):
        x = rng.randn(rows, 8).astype(np.float32)
        outs.append(engine.infer([x], timeout=240)[0])
    stats = engine.stats()
    engine.close()
    if rank == 0:
        rec = {"mesh": stats["mesh"],
               "compiles": stats["compiles"],
               "shas": [_sha(o) for o in outs],
               "world": dist.get_world_size()}
        path = os.path.join(outdir, "rank0.json")
        with open(path + ".tmp", "w") as f:
            json.dump(rec, f)
        os.replace(path + ".tmp", path)


def main():
    mode = sys.argv[1]
    if mode == "rank":
        # launched by launch_collective: ONE device per process, the
        # mesh spans processes (real gloo collectives)
        os.environ["XLA_FLAGS"] = " ".join(
            [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(
                 "--xla_force_host_platform_device_count")]
            + ["--xla_force_host_platform_device_count=1"])
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        run_rank(sys.argv[2], sys.argv[3])
        return
    _setup_devices()
    if mode == "contract":
        run_contract(sys.argv[2], sys.argv[3:])
    elif mode == "decode":
        run_decode(sys.argv[2], sys.argv[3])
    elif mode == "serve":
        run_serve(sys.argv[2], sys.argv[3])
    else:
        raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    main()
