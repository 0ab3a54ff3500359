#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, on every local chip, at the full width of BERT-base (depth and
weights are as published / random from a seed):

  0  paddle.utils.run_check()            eager tape step + tiny SPMD step
  1  train   BertForPretraining, seq 128, 256 rows per chip, amp O1,
             AdamW + global-norm clip, donation, batches from
             io.DataLoader(num_workers=2), spmd.build_train_step on dp=N
  2  serve   jit.save (batch-polymorphic) -> serve_model(dynamic_batching)
             answering socket clients; with N > 1 once more on mesh tp<N>
  3  kernels scaled_dot_product_attention (causal, bf16, fwd + bwd) at
             b8 h12 s4096 d64 and b4 h32 s2048 d128, dropout 0 and 0.1,
             compiled by Mosaic and checked against the XLA reference;
             packed_self_attention (the whole-sequence kernel) at the BERT
             cells' b256 h12 s128 d64 likewise, alone and on the dp=N mesh;
             then one train step of a 4-layer Llama block stack at seq 2048
  4  decode  DecodeEngine behind PredictorServer, toy decoder (hidden
             256): a does-it-run-on-the-device check at toy width
  5  N >= 4  the hybrid-parallel rows of __graft_entry__.dryrun_multichip
             on the real devices

It sets neither JAX_PLATFORMS nor jax_platforms. Without a TPU it exits 2
at once, having run nothing. ``--dry-run`` is the CPU rehearsal (toy
widths, Pallas interpreter): it labels itself ``"dry_run": true`` and is
what tier-1 exercises. Times printed are smoke observations (``smoke_*``
keys), not metrics.

stdout: a header line, one JSON line per phase, and as the last line
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Exit code 0 only
if every phase passed and paddle_tpu raised no RuntimeWarning.
"""
import argparse
import collections
import contextlib
import faulthandler
import json
import os
import socket
import struct
import sys
import tempfile
import threading
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the process must be gone before the driver's 1200 s limit: on a hang,
#: dump every thread's stack and exit non-zero
WATCHDOG_S = 1150

FULL = dict(
    bert={},                        # the models' defaults = BERT-base:
    #                                 hidden 768, 12 layers, 12 heads,
    #                                 FFN 3072, vocab 30522
    seq=128, rows_per_chip=256, max_pred=20, warmup=3, steps=10,
    serve_requests=36, serve_clients=4,
    # (batch, heads, seq, head width[, value width]): the last is latent
    # attention's core at the JoyAI cell's shape, keys wider than values
    attn_shapes=((8, 12, 4096, 64), (4, 32, 2048, 128),
                 (1, 32, 8192, 192, 128)),
    short_shape=(256, 12, 128, 64),  # the BERT cells' attention, per chip
    # one Kimi Delta Attention layer at the Kimi-Linear cell's head sizes
    kda=dict(hidden_size=2304, num_heads=32, head_dim=128), kda_seq=2048,
    # one Mamba-2 mixer at the granite cell's sizes: 64 heads of 64 on a
    # state of 128, one group
    ssd=dict(hidden_size=2048, num_heads=64, head_dim=64, d_state=128),
    ssd_seq=2048,
    llama=dict(vocab_size=32000, hidden_size=1024, num_layers=4,
               num_heads=8, intermediate_size=2816, max_seq_len=2048),
    llama_seq=2048, llama_rows_per_chip=2,
    decode=dict(hidden=256, vocab=512), decode_slots=8, decode_max_seq=64,
    decode_streams=12, decode_new_tokens=16,
)
TOY = dict(
    bert=dict(vocab_size=512, hidden_size=64, num_hidden_layers=1,
              num_attention_heads=4, intermediate_size=128,
              max_position_embeddings=64),
    seq=32, rows_per_chip=4, max_pred=4, warmup=3, steps=10,
    serve_requests=12, serve_clients=2,
    attn_shapes=((1, 2, 256, 64), (1, 2, 256, 24, 16)),
    short_shape=(4, 2, 128, 64),
    # one lane group a head and over one chunk: the kernels' shortest case
    kda=dict(hidden_size=64, num_heads=2, head_dim=128), kda_seq=72,
    # values of half a lane group on a state of one, a token block and a
    # part: the scan kernels' shortest case
    ssd=dict(hidden_size=64, num_heads=8, head_dim=64, d_state=128),
    ssd_seq=300,
    llama=dict(vocab_size=256, hidden_size=64, num_layers=1, num_heads=2,
               intermediate_size=128, max_seq_len=256),
    llama_seq=256, llama_rows_per_chip=1,
    decode=dict(hidden=32, vocab=64), decode_slots=4, decode_max_seq=32,
    decode_streams=12, decode_new_tokens=8,
)

# ---- tolerances, each with its reason -------------------------------
# Served reply vs the direct (eager, op-by-op) forward of the same
# weights; outputs are layer-normed, O(1). On TPU f32 matmuls run as bf16
# MXU passes at jax's default precision: an f32-level difference in one
# layer's output (a tp mesh sums partial products in another order) can
# flip the bf16 rounding of the next matmul's input (4e-3 relative), and
# 12 layers compound it. Observed on the v5e (PR 21): 0.0 or 7.4e-3 on a
# single chip (by bucket), 1.2e-2 on tp4; a wrong program is off by O(1).
# On CPU both sides are f32.
SERVE_ATOL = {"tpu": 5e-2, "cpu": 1e-4}
# Flash kernel vs _sdpa_ref, bf16 in/out (eps 2^-8 = 3.9e-3): the kernel
# keeps probabilities in f32 until the P@V product and accumulates
# blockwise (online softmax); the reference does one f32 softmax, casts
# to bf16, then multiplies. Errors are judged against the largest
# reference magnitude of each tensor.
ATTN_REL_TOL = 3e-2
# A Kimi Delta Attention layer under amp O1 on the scan (the Mosaic kernels,
# and the XLA scan beside them) vs the same weights in float32 on the token
# recurrence: bf16 projections, q, k, v and
# chunk operands (eps 3.9e-3 each) through a state that sums 2,048 tokens'
# writes; judged against each tensor's largest magnitude. A wrong chunk
# boundary or decay is off by O(1).
KDA_REL_TOL = 5e-2
# Dropout keep fraction over >= 6.5e4 causal entries (toy) / 8e8 (full):
# binomial noise is ~1e-3 at most; bf16 rounding of the row sums it is
# read from adds < 4e-3. (Observed on the v5e, PR 21: 0.8985.)
KEEP_FRAC_TOL = 1e-2


def _rel_err(got, ref):
    """Largest absolute difference over the largest reference magnitude."""
    import jax.numpy as jnp
    import numpy as np

    got = np.asarray(got.astype(jnp.float32))
    ref = np.asarray(ref.astype(jnp.float32))
    assert np.isfinite(got).all()
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-6))


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("server closed the connection")
        buf += chunk
    return bytes(buf)


def _read_reply(sock):
    (blen,) = struct.unpack("<I", _recv_exact(sock, 4))
    body = _recv_exact(sock, blen)
    return body[0], body[1:]


def _compile_seconds():
    """Lowering + backend-compile seconds so far: what the program's own
    bridge from ``jax.monitoring`` (``obs/ledger.py``) has put into the span
    layer, so each phase line can say how much of its wall time was
    compilation (a cache read counts as one). Python tracing is left in the
    rest."""
    from paddle_tpu.obs import tracing

    totals = {r["name"]: r["total"] for r in tracing.summary_rows()}
    return totals.get("compile.lower", 0.0) + totals.get("compile.backend",
                                                         0.0)


def _cache_events(since):
    """(executables read from the persistent cache, executables written to
    it) by the ``compile.backend`` spans that ended after ``since`` (on
    ``time.monotonic``, the span layer's clock), from their ``cache``
    attribute; (None, None) where the ring may have dropped some of them."""
    from paddle_tpu.obs import tracing

    spans = tracing.finished()
    if tracing.ring_full() and spans[0]["t1"] > since:
        return None, None
    said = collections.Counter(
        s["attrs"]["cache"] for s in spans
        if s["t1"] > since and s["name"] == "compile.backend")
    return said["hit"], said["written"]


class Smoke:
    def __init__(self, dry_run):
        import jax

        self.dry_run = dry_run
        self.cfg = TOY if dry_run else FULL
        self.devices = jax.local_devices()
        self.n = len(self.devices)
        self.platform = self.devices[0].platform
        self.failed = []
        self.warned = []  # every warning shown: (category, filename, text)
        self.scratch = tempfile.TemporaryDirectory(prefix="chip_smoke_")
        show = warnings.showwarning

        def showwarning(message, category, filename, lineno, *a, **kw):
            self.warned.append((category, os.path.abspath(filename),
                                f"{filename}:{lineno}: {message}"))
            show(message, category, filename, lineno, *a, **kw)

        warnings.showwarning = showwarning
        warnings.simplefilter("always", RuntimeWarning)

    def runtime_warnings_from_paddle_tpu(self):
        """A warned-about fallback is how a broken path hides: any
        RuntimeWarning raised from the package fails the run."""
        ours = os.path.join(ROOT, "paddle_tpu") + os.sep
        return [text for cat, filename, text in self.warned
                if issubclass(cat, RuntimeWarning)
                and filename.startswith(ours)]

    def emit(self, obj):
        print(json.dumps(obj), flush=True)

    def run_phase(self, idx, name, fn):
        c0, mark = _compile_seconds(), time.monotonic()
        t0 = time.perf_counter()
        rec = {"phase": idx, "name": name}
        try:
            # phases may print (run_check, dryrun_multichip): stdout
            # carries only the header, the phase lines and the result
            with contextlib.redirect_stdout(sys.stderr):
                rec.update(fn() or {})
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 - a failed phase is reported, the rest still run
            traceback.print_exc(file=sys.stderr)
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"[:2000]
            self.failed.append(idx)
        compile_s = _compile_seconds() - c0
        wall = time.perf_counter() - t0
        rec["smoke_wall_s"] = round(wall, 2)
        rec["smoke_compile_s"] = round(compile_s, 2)
        rec["smoke_rest_s"] = round(wall - compile_s, 2)
        rec["cache_hits"], rec["cache_writes"] = _cache_events(mark)
        self.emit(rec)

    # ------------------------------------------------------------ 0
    def phase_run_check(self):
        import numpy as np
        import paddle_tpu as paddle

        paddle.utils.run_check()
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        y = paddle.matmul(x, x)
        want_place = paddle.CPUPlace if self.dry_run else paddle.TPUPlace
        want_dev = "cpu" if self.dry_run else "tpu:0"
        assert isinstance(y.place, want_place), y.place
        assert paddle.get_device() == want_dev, paddle.get_device()
        assert float(y.numpy()[0, 0]) == 4.0
        return {"place": repr(y.place), "device": paddle.get_device()}

    # ------------------------------------------------------------ 1
    def phase_train(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import io, nn, optimizer
        from paddle_tpu.distributed import spmd, topology
        from paddle_tpu.text.models import BertForPretraining

        cfg, n, devs = self.cfg, self.n, self.devices
        seq, max_pred = cfg["seq"], cfg["max_pred"]
        rows = cfg["rows_per_chip"] * n
        n_batches = cfg["warmup"] + cfg["steps"] + 2  # + the two timed ways
        t_phase = time.perf_counter()
        mark = time.monotonic()
        n_warned = len(self.warned)

        paddle.seed(0)
        model = BertForPretraining(hidden_dropout_prob=0.1,
                                   attention_probs_dropout_prob=0.1,
                                   **cfg["bert"])
        vocab = model.bert.vocab_size
        opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                              weight_decay=0.01,
                              grad_clip=nn.ClipGradByGlobalNorm(1.0))

        class PackedMLM(nn.Layer):
            """build_train_step feeds one input array: [ids | masked
            positions] packed along dim 1, split inside the traced fwd."""

            def __init__(self, inner):
                super().__init__()
                self.inner = inner

            def forward(self, packed):
                mlm_logits, _ = self.inner(
                    packed[:, :seq], masked_positions=packed[:, seq:])
                return mlm_logits

        def mlm_loss(logits, labels):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(
                jnp.take_along_axis(logp, labels[..., None], axis=-1))

        class SyntheticMLM(io.Dataset):
            """Seeded synthetic numpy samples. Sample i repeats sample
            i % rows, so every batch the workers deliver is the same
            batch and the loss on it has to fall."""

            def __init__(self):
                rng = np.random.RandomState(0)
                ids = rng.randint(0, vocab, (rows, seq))
                pos = np.stack([rng.choice(seq, max_pred, replace=False)
                                for _ in range(rows)])
                self.packed = np.concatenate([ids, pos], 1).astype(np.int32)
                self.labels = rng.randint(
                    0, vocab, (rows, max_pred)).astype(np.int32)

            def __len__(self):
                return rows * n_batches

            def __getitem__(self, i):
                return self.packed[i % rows], self.labels[i % rows]

        mesh = topology.build_mesh(dp=n, devices=devs)
        step_fn, init_fn = spmd.build_train_step(
            PackedMLM(model), mlm_loss, opt, mesh=mesh, amp_level="O1",
            donate=True)
        params, opt_state = init_fn()
        state_bytes = sum(a.nbytes for a in jax.tree.leaves(
            (params, opt_state)))
        eager_bytes = sum(p._value.nbytes for p in model.parameters())

        # the fork happens here, with the backend initialised and the
        # chip held by this process
        loader = io.DataLoader(SyntheticMLM(), batch_size=rows,
                               shuffle=False, num_workers=2)
        batches = iter(loader)
        key = jax.random.PRNGKey(0)
        losses, info = [], {}

        def step(i):
            nonlocal params, opt_state
            packed, labels = next(batches)
            x, y = (spmd.shard_batch(a, mesh) for a in (packed, labels))
            k = jax.random.fold_in(key, i)
            loss, params, opt_state = step_fn(params, opt_state, x, y, key=k)
            return loss, (x, y, k)

        for i in range(cfg["warmup"] + cfg["steps"]):
            loss, (x, y, k) = step(i)
            losses.append(float(loss))
            if i == 0:
                info["smoke_time_to_first_step_s"] = round(
                    time.perf_counter() - t_phase, 2)
                (info["first_step_cache_hits"],
                 info["first_step_cache_writes"]) = _cache_events(mark)
                # what the compiler did with the donation: bytes of
                # output that alias an input (a persistent-cache read of
                # the executable the step just compiled)
                mem = step_fn.jitted.lower(
                    params, opt_state, {}, x, y, k,
                    jnp.float32(opt.get_lr())).compile().memory_analysis()
                info["compiled_alias_bytes"] = mem.alias_size_in_bytes
                info["compiled_temp_bytes"] = mem.temp_size_in_bytes
        # one step timed to block_until_ready, then the scalar read on top
        # of it (is block_until_ready a barrier here?) ...
        t0 = time.perf_counter()
        loss, _ = step(len(losses))
        t1 = time.perf_counter()
        jax.block_until_ready(loss)
        t2 = time.perf_counter()
        float(loss)
        t3 = time.perf_counter()
        info["smoke_step_dispatch_s"] = round(t1 - t0, 4)
        info["smoke_step_to_block_until_ready_s"] = round(t2 - t0, 4)
        info["smoke_readback_after_block_s"] = round(t3 - t2, 4)
        # ... and one step timed to the device->host scalar read
        t0 = time.perf_counter()
        float(step(len(losses) + 1)[0])
        info["smoke_step_to_scalar_readback_s"] = round(
            time.perf_counter() - t0, 4)
        assert next(batches, None) is None  # exhausted: the workers exit

        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], \
            f"loss did not fall on a repeated batch: {losses}"
        # donation took effect: jax warns when XLA cannot alias a donated
        # buffer, and the compiled step must alias one copy of params +
        # AdamW state in place, not hold two (CPU implements no donation)
        if not self.dry_run:
            unusable = [text for _, _, text in self.warned[n_warned:]
                        if "donated buffers were not usable" in text]
            assert not unusable, unusable
            assert info["compiled_alias_bytes"] >= 0.99 * state_bytes, \
                (info["compiled_alias_bytes"], state_bytes)

        leaves = jax.tree.leaves((params, opt_state))
        assert all(d.platform == self.platform
                   for a in leaves for d in a.devices())
        assert all(a.sharding.device_set == set(devs) for a in leaves), \
            "a parameter or optimizer state misses a device of the mesh"
        stats = [d.memory_stats() for d in devs]
        if all(s and "bytes_in_use" in s for s in stats):
            in_use = [s["bytes_in_use"] for s in stats]
            # one copy on each device: the step's params + both AdamW
            # moments (dp replicates them), plus the layer's own eager
            # weights on device 0. A second live copy of the state
            # (params not donated in place, or aliased twice) would
            # show here.
            expect = state_bytes + eager_bytes
            assert all(b > 0 for b in in_use), in_use
            assert in_use[0] < expect + state_bytes // 2, (in_use, expect)
            info["bytes_in_use"] = in_use
            info["peak_bytes_in_use"] = [s.get("peak_bytes_in_use")
                                         for s in stats]
            info["expected_one_copy_bytes"] = expect
        else:
            info["bytes_in_use"] = "not reported by this backend"
        info["jax_fork_warnings"] = sum(
            "os.fork() was called" in text
            for _, _, text in self.warned[n_warned:])
        info.update(model="bert-base" if not cfg["bert"] else "bert-toy",
                    mesh=f"dp{n}", rows=rows, seq=seq,
                    steps=len(losses), loss_first=round(losses[0], 4),
                    loss_last=round(losses[-1], 4),
                    train_state_bytes=state_bytes)
        return info

    # ------------------------------------------------------------ 2
    def phase_serve(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.inference import wire_spec as ws
        from paddle_tpu.inference.server import serve_model
        from paddle_tpu.static import InputSpec
        from paddle_tpu.text.models import BertModel

        cfg, seq = self.cfg, self.cfg["seq"]
        paddle.seed(1)
        model = BertModel(**cfg["bert"])
        model.eval()
        prefix = os.path.join(self.scratch.name, "bert")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([None, seq], "int32")])
        with open(prefix + ".pdmeta.json") as f:
            meta = json.load(f)
        assert meta["format"] == "stablehlo" and meta["polymorphic"], meta

        # the direct forward, once, over a pool of 8 distinct rows; each
        # request sends 1-8 of them (BERT rows do not interact)
        rng = np.random.RandomState(2)
        pool = rng.randint(0, model.vocab_size, (8, seq)).astype(np.int32)
        ref_seq, ref_pooled = (t.numpy() for t in model(
            paddle.to_tensor(pool)))
        atol = SERVE_ATOL.get(self.platform, SERVE_ATOL["tpu"])
        picks = [rng.choice(8, rng.randint(1, 9), replace=False)
                 for _ in range(cfg["serve_requests"])]

        def stats_of(port):
            with socket.create_connection(("127.0.0.1", port)) as s:
                s.sendall(ws.build_request(ws.CMD_STATS))
                status, body = _read_reply(s)
            assert status == ws.STATUS_OK, status
            return json.loads(body)

        def drive(mesh):
            server = serve_model(prefix, dynamic_batching=True,
                                 max_batch_size=8, mesh=mesh)
            errs, worst = [], [0.0]
            try:
                warm = stats_of(server.port)
                buckets = warm["declared_buckets"]
                assert warm["compiles"] == len(buckets) > 0, warm

                def client(mine):
                    try:
                        with socket.create_connection(
                                ("127.0.0.1", server.port)) as s:
                            for idx in mine:
                                s.sendall(ws.build_request(
                                    ws.CMD_INFER,
                                    ws.encode_arrays([pool[idx]])))
                                status, body = _read_reply(s)
                                assert status == ws.STATUS_OK, (
                                    status, body[:200])
                                got_seq, got_pooled = ws.decode_arrays(body)
                                for got, ref in ((got_seq, ref_seq[idx]),
                                                 (got_pooled,
                                                  ref_pooled[idx])):
                                    assert got.shape == ref.shape
                                    assert np.isfinite(got).all()
                                    err = float(np.abs(got - ref).max())
                                    worst[0] = max(worst[0], err)
                                    assert err <= atol, (err, atol)
                    except Exception as e:  # noqa: BLE001 - joined and re-raised below
                        errs.append(e)

                k = cfg["serve_clients"]
                threads = [threading.Thread(target=client,
                                            args=(picks[i::k],))
                           for i in range(k)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(300)
                assert not any(t.is_alive() for t in threads), \
                    "a serving client did not finish"
                if errs:
                    raise errs[0]
                after = stats_of(server.port)
                assert after["requests"] >= len(picks), after["requests"]
                assert after["compiles"] == warm["compiles"], \
                    (f"traffic compiled {after['compiles'] - warm['compiles']}"
                     " bucket program(s) warm-up should have covered")
                assert after["mesh"] == (mesh or "single"), after["mesh"]
            finally:
                t0 = time.perf_counter()
                server.stop()
                drain_s = time.perf_counter() - t0
            return {"buckets": buckets, "warmup_compiles": warm["compiles"],
                    "traffic_compiles": after["compiles"] - warm["compiles"],
                    "requests": len(picks),
                    "max_abs_err": round(worst[0], 6),
                    "smoke_drain_s": round(drain_s, 2)}

        info = {"format": meta["format"], "polymorphic": meta["polymorphic"],
                "atol": atol, "single": drive(None)}
        if self.n > 1:
            info[f"tp{self.n}"] = drive(f"tp{self.n}")
        return info

    # ------------------------------------------------------------ 3
    def phase_kernels(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu import optimizer
        from paddle_tpu.distributed import spmd, topology
        from paddle_tpu.text.models import LlamaModel

        def attention(q, k, v, p, seed):
            """fwd + bwd through the eager tape; the seed feeds the
            framework RNG the dropout mask is drawn from."""
            paddle.seed(seed)
            ts = [paddle.to_tensor(a, stop_gradient=False)
                  for a in (q, k, v)]
            out = F.scaled_dot_product_attention(
                *ts, dropout_p=p, is_causal=True, training=True)
            # a fixed non-uniform cotangent so the three grads are not
            # degenerate
            w = jnp.cos(jnp.arange(out.shape[-1], dtype=jnp.float32))
            (out.astype("float32") * paddle.to_tensor(w)).sum().backward()
            return [out._value] + [t.grad._value for t in ts]

        def mosaic_calls(shape, p):
            """Lower (no compile) the same fwd + bwd and count Mosaic
            custom calls: 2 = forward, and the backward that takes dq, dk
            and dv from one pass over the score tiles."""
            def loss(q, k, v):
                out = F.scaled_dot_product_attention(
                    paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
                    dropout_p=p, is_causal=True, training=True)
                return jnp.sum(out._value.astype(jnp.float32))

            spec = jax.ShapeDtypeStruct(shape[:4], jnp.bfloat16)
            v_spec = jax.ShapeDtypeStruct(shape[:3] + shape[-1:],
                                          jnp.bfloat16)
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                spec, spec, v_spec).as_text()
            paddle.seed(0)  # the trace drew its dropout key from the
            #                 global generator: drop the tracer it left
            return text.count("tpu_custom_call")

        info = {}
        for shape in self.cfg["attn_shapes"]:
            b, h, s, d = shape[:4]
            v_shape = shape[:3] + shape[-1:]
            tag = f"b{b}h{h}s{s}d{d}" + (f"v{shape[4]}" if shape[4:] else "")
            rng = np.random.RandomState(3)
            q, k, v = (jnp.asarray(rng.randn(*sh) * 0.5, jnp.bfloat16)
                       for sh in (shape[:4], shape[:4], v_shape))
            rec = {}
            for p in (0.0, 0.1):
                n_calls = mosaic_calls(shape, p)
                if self.dry_run:
                    assert n_calls == 0, n_calls  # interpreter, by flag
                else:
                    assert n_calls == 2, \
                        f"{tag} p={p}: {n_calls} Mosaic calls lowered"
                rec[f"mosaic_calls_p{p}"] = n_calls

            # dropout 0: kernel at the full shape vs the XLA reference on
            # the first and the last (batch, head-pair) slice — (b, h)
            # programs are independent and the reference's S^2 logits for
            # the full shape would not fit the chip
            got = attention(q, k, v, 0.0, seed=0)
            worst = 0.0
            paddle.set_flags({"use_pallas_kernels": False})
            try:
                for bs, hs in ((slice(0, 1), slice(0, 2)),
                               (slice(b - 1, b), slice(h - 2, h))):
                    ref = attention(q[bs, hs], k[bs, hs], v[bs, hs], 0.0,
                                    seed=0)
                    for g, r in zip(got, ref):
                        worst = max(worst, _rel_err(g[bs, hs], r))
            finally:
                paddle.set_flags({"use_pallas_kernels": True})
            assert worst <= ATTN_REL_TOL, (tag, worst)
            rec["max_rel_err_vs_sdpa_ref"] = round(worst, 5)

            # dropout 0.1: same seed -> same bits, another seed -> other
            # mask, and the expected fraction kept
            a1 = attention(q, k, v, 0.1, seed=11)
            a2 = attention(q, k, v, 0.1, seed=11)
            a3 = attention(q, k, v, 0.1, seed=12)
            assert all(np.isfinite(np.asarray(t.astype(jnp.float32))).all()
                       for t in a1)
            assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a1, a2)), \
                f"{tag}: same seed gave different dropout results"
            assert not bool(jnp.array_equal(a1[0], a3[0])), \
                f"{tag}: a different seed gave the same dropout mask"
            # q = k = 0 makes every causal row uniform, v = 1 makes the
            # output the kept share of that row: out[r] = kept_r /
            # ((r + 1) * 0.9)
            zeros = jnp.zeros(shape[:4], jnp.bfloat16)
            out = attention(zeros, zeros, jnp.ones(v_shape, jnp.bfloat16),
                            0.1, seed=13)[0]
            row_len = np.arange(1, s + 1, dtype=np.float64)
            kept = (np.asarray(out[..., 0].astype(jnp.float32), np.float64)
                    * row_len * 0.9).sum()
            frac = kept / (b * h * row_len.sum())
            assert abs(frac - 0.9) <= KEEP_FRAC_TOL, (tag, frac)
            rec["dropout_keep_fraction"] = round(float(frac), 5)
            info[tag] = rec

        info["short"] = self.short_kernel()
        info["kda"] = self.delta_layer()
        info["ssd"] = self.scan_layer()

        # the kernel inside the framework's own tape, amp and donation
        n, devs = self.n, self.devices
        seq = self.cfg["llama_seq"]
        rows = self.cfg["llama_rows_per_chip"] * n
        paddle.seed(4)
        llama = LlamaModel(**self.cfg["llama"])
        vocab = self.cfg["llama"]["vocab_size"]
        opt = optimizer.AdamW(1e-4, parameters=llama.parameters())

        def lm_loss(logits, labels):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.mean(
                jnp.take_along_axis(logp, labels[..., None], axis=-1))

        mesh = topology.build_mesh(dp=n, devices=devs)
        step_fn, init_fn = spmd.build_train_step(
            llama, lm_loss, opt, mesh=mesh, amp_level="O1", donate=True)
        params, opt_state = init_fn()
        rng = np.random.RandomState(5)
        ids = spmd.shard_batch(
            rng.randint(0, vocab, (rows, seq)).astype(np.int32), mesh)
        lowered = step_fn.jitted.lower(
            params, opt_state, {}, ids, ids, jax.random.PRNGKey(0),
            jnp.float32(1e-4)).as_text()
        n_calls = lowered.count("tpu_custom_call")
        layers = self.cfg["llama"]["num_layers"]
        if not self.dry_run:
            # a layer's attention: the forward and the one-pass backward
            assert n_calls >= 2 * layers, \
                f"llama step lowered {n_calls} Mosaic calls, want >= " \
                f"{2 * layers}"
        loss0, params, opt_state = step_fn(params, opt_state, ids, ids)
        loss1, params, opt_state = step_fn(params, opt_state, ids, ids)
        loss0, loss1 = float(loss0), float(loss1)
        assert np.isfinite([loss0, loss1]).all() and loss1 < loss0, \
            (loss0, loss1)
        info["llama_step"] = {
            "mesh": f"dp{n}", "rows": rows, "seq": seq, "layers": layers,
            "mosaic_calls": n_calls, "loss": [round(loss0, 4),
                                              round(loss1, 4)]}
        return info

    def delta_layer(self):
        """One ``KimiDeltaAttention`` layer, forward and backward through
        the eager tape, amp O1: on the path the route gives the gated delta
        rule (the Mosaic kernels ``kda_chunk_fwd`` / ``_bwd`` — what the
        Kimi-Linear cell's step runs) and on the XLA scan it falls back to,
        each against float32 on the recurrence over tokens, the same
        weights and input."""
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.ops import linear_attention
        from paddle_tpu.text.models import KimiDeltaAttention

        seq, d = self.cfg["kda_seq"], self.cfg["kda"]["head_dim"]
        paddle.seed(6)
        layer = KimiDeltaAttention(**self.cfg["kda"])
        return self._layer_on_its_paths(
            layer, self.cfg["kda"], seq, 6, "core_path",
            linear_attention.core_path(seq, d, d, jnp.bfloat16),
            linear_attention._CORE_TOTAL,
            lambda: (layer.A_log, layer.q_conv.weight))

    def scan_layer(self):
        """One ``Mamba2Mixer``, likewise: on the path the route gives the
        selective state-space scan (the Mosaic kernels ``ssd_chunk_fwd`` /
        ``_bwd`` — what the granite cell's step runs) and on the XLA scan it
        falls back to, each against float32 on the recurrence."""
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.ops import linear_attention
        from paddle_tpu.text.models import Mamba2Mixer

        seq, cfg = self.cfg["ssd_seq"], self.cfg["ssd"]
        paddle.seed(7)
        layer = Mamba2Mixer(**cfg)
        return self._layer_on_its_paths(
            layer, cfg, seq, 7, "ssd_path",
            linear_attention.ssd_path(seq, cfg["num_heads"], 1,
                                      cfg["head_dim"], cfg["d_state"],
                                      jnp.bfloat16),
            linear_attention._SSD_TOTAL,
            lambda: (layer.A_log, layer.D, layer.conv1d.weight))

    def _layer_on_its_paths(self, layer, cfg, seq, seed, route, given,
                            counter, parameters):
        """A linear-time mixer forward and backward through the eager tape
        on the path its route (``ops.linear_attention.<route>``) gives under
        amp O1, on the XLA scan (``chunked``) and, in float32, on the
        recurrence over tokens the two are held to; each counted once in
        ``counter`` under its own label. Compared: the output, the input's
        gradient and those of ``parameters()``."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.ops import linear_attention

        x = np.random.RandomState(seed).randn(
            1, seq, cfg["hidden_size"]).astype(np.float32)
        # an eager call on a host with several chips does not know its
        # program's devices: there the XLA scan is what the route gives
        assert given == ("kernel" if self.dry_run or jax.device_count() == 1
                         else "chunked"), given
        counts, times = {}, {}

        def run(path, amp):
            label = path or given               # None: the route's own choice
            before = counter.value(path=label)
            saved = getattr(linear_attention, route)
            if path:
                setattr(linear_attention, route, lambda *shape: path)
            t0 = time.monotonic()
            try:
                layer.clear_gradients()
                t = paddle.to_tensor(x, stop_gradient=False)
                with paddle.amp.auto_cast(enable=amp, level="O1",
                                          dtype="bfloat16"):
                    out = layer(t)
                w = jnp.cos(jnp.arange(out.shape[-1], dtype=jnp.float32))
                (out.astype("float32") * paddle.to_tensor(w)).sum().backward()
            finally:
                setattr(linear_attention, route, saved)
            out = [out._value, t.grad._value,
                   *(p.grad._value for p in parameters())]
            jax.block_until_ready(out)
            times[label] = time.monotonic() - t0
            counts[label] = counts.get(label, 0) + (
                counter.value(path=label) - before)
            return out

        got, fallback = run(None, amp=True), run("chunked", amp=True)
        ref = run("recurrent", amp=False)
        assert counts == collections.Counter(
            [given, "chunked", "recurrent"]), counts
        worst = {path: max(_rel_err(g, r) for g, r in zip(outs, ref))
                 for path, outs in ((given, got), ("chunked", fallback))}
        assert max(worst.values()) <= KDA_REL_TOL, worst
        return {"seq": seq, **cfg, "path": given,
                "max_rel_err_vs_recurrence": {
                    p: round(e, 5) for p, e in worst.items()},
                "smoke_fwd_bwd_s": {p: round(times[p], 3)
                                    for p in worst}}

    def short_kernel(self):
        """The whole-sequence kernel at the BERT cells' attention shape,
        bf16, through packed_self_attention: Mosaic calls lowered (forward
        + ONE backward), agreement with the XLA route at dropout 0, the
        dropout mask's determinism and keep fraction, and the same call on
        the dp=N mesh against the one-device result (masks included)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as paddle
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.core import random as random_core
        from paddle_tpu.distributed import topology
        from paddle_tpu.ops import attention as attn_ops

        b, h, s, d = self.cfg["short_shape"]
        e = h * d
        rng = np.random.RandomState(7)
        qkv = jnp.asarray(rng.randn(b, s, 3 * e) * 0.5, jnp.bfloat16)
        w = jnp.cos(jnp.arange(e, dtype=jnp.float32))

        def loss(qkv, p):
            out = attn_ops.packed_self_attention(
                paddle.Tensor(qkv), h, dropout_p=p, training=True)._value
            return jnp.sum(out.astype(jnp.float32) * w), out

        # a program says which devices it is for (the gate picks no
        # kernel for a jit that GSPMD might partition): one, or the mesh
        one = topology.build_mesh(dp=1, devices=self.devices[:1])

        def jitted(p, seed, mesh=None):
            """(grad, out) of the call as one program; the key feeds the
            mask."""
            def fn(qkv):
                with random_core.rng_guard(jax.random.PRNGKey(seed)), \
                        topology.tracing_for(mesh or one):
                    return jax.grad(loss, has_aux=True)(qkv, p)
            if mesh is None:
                return jax.jit(fn)
            return jax.jit(fn, in_shardings=(NamedSharding(mesh, P("dp")),))

        def run(qkv, p, seed, mesh=None):
            return jitted(p, seed, mesh)(qkv)

        rec = {"shape": [b, h, s, d]}
        before = attn_ops._ROUTE_TOTAL.value(route="short")
        for p in (0.0, 0.1):
            n_calls = jitted(p, 0).lower(qkv).as_text().count(
                "tpu_custom_call")
            assert n_calls == (0 if self.dry_run else 2), \
                f"short p={p}: {n_calls} Mosaic calls lowered"
            rec[f"mosaic_calls_p{p}"] = n_calls
        assert attn_ops._ROUTE_TOTAL.value(route="short") == before + 2

        got = run(qkv, 0.0, 0)
        paddle.set_flags({"use_pallas_kernels": False})
        try:
            ref = run(qkv, 0.0, 0)
        finally:
            paddle.set_flags({"use_pallas_kernels": True})
        worst = max(_rel_err(g, r) for g, r in zip(got, ref))
        assert worst <= ATTN_REL_TOL, ("short", worst)
        rec["max_rel_err_vs_sdpa_ref"] = round(worst, 5)

        a1, a2, a3 = run(qkv, 0.1, 11), run(qkv, 0.1, 11), run(qkv, 0.1, 12)
        assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a1, a2)), \
            "short: same key gave different dropout results"
        assert not bool(jnp.array_equal(a1[1], a3[1])), \
            "short: a different key gave the same dropout mask"
        # q = k = 0, v = 1: every row is uniform, the output its kept
        # share / 0.9
        ones = jnp.concatenate([jnp.zeros((b, s, 2 * e), jnp.bfloat16),
                                jnp.ones((b, s, e), jnp.bfloat16)], axis=-1)
        out = run(ones, 0.1, 13)[1]
        frac = float(np.asarray(out[:, :, ::d].astype(jnp.float32),
                                np.float64).mean() * 0.9)
        assert abs(frac - 0.9) <= KEEP_FRAC_TOL, ("short", frac)
        rec["dropout_keep_fraction"] = round(frac, 5)

        if self.n > 1:
            # b rows a chip on dp=N: the shard_map wrapper, rows of every
            # shard equal to the one-device result
            mesh = topology.build_mesh(dp=self.n, devices=self.devices)
            wide = jnp.concatenate([qkv] * self.n, axis=0)
            on_mesh = run(wide, 0.0, 0, mesh=mesh)
            worst = max(_rel_err(m[i * b:(i + 1) * b], g)
                        for m, g in zip(on_mesh, got) for i in range(self.n))
            assert worst <= 1e-6, ("short on mesh", worst)
            # the mask hashes the rows' numbers in the whole batch: two
            # shards do not drop the same entries, and the mesh does not
            # change what is dropped
            wide_ones = jnp.concatenate([ones] * self.n, axis=0)
            od = run(wide_ones, 0.1, 13, mesh=mesh)[1]
            assert not bool(jnp.array_equal(od[:b], od[b:2 * b])), \
                "short on mesh: two shards drew the same mask"
            assert bool(jnp.array_equal(od, run(wide_ones, 0.1, 13)[1])), \
                "short on mesh: the mask depends on the mesh"
            rec[f"dp{self.n}_max_rel_err_vs_one_device"] = round(worst, 8)
        return rec

    # ------------------------------------------------------------ 4
    def phase_decode(self):
        import numpy as np
        from paddle_tpu.inference import wire_spec as ws
        from paddle_tpu.inference.decode import DecodeEngine
        from paddle_tpu.inference.server import PredictorServer

        sys.path.insert(0, os.path.join(ROOT, "tests"))
        try:
            from decode_worker import reference_decode, toy_decode_model
        finally:
            sys.path.pop(0)

        cfg = self.cfg
        vocab, new = cfg["decode"]["vocab"], cfg["decode_new_tokens"]
        model = toy_decode_model(seed=0, **cfg["decode"])
        engine = DecodeEngine(model, max_slots=cfg["decode_slots"],
                              max_seq_len=cfg["decode_max_seq"],
                              max_prompt_len=16, name="chip-smoke-decode")
        engine.warmup()
        server = PredictorServer(lambda *a: list(a), decode_engine=engine,
                                 own_decode_engine=True)
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, vocab, rng.randint(3, 15)).astype(np.int32)
                   for _ in range(cfg["decode_streams"])]
        streams, errs = [None] * len(prompts), []

        def stream(i):
            try:
                chunks = []
                with socket.create_connection(
                        ("127.0.0.1", server.port)) as s:
                    s.sendall(ws.build_request(
                        ws.CMD_INFER, ws.encode_arrays([prompts[i]])
                        + ws.encode_decode_opts(new)))
                    while True:
                        status, body = _read_reply(s)
                        assert status in (ws.STATUS_OK, ws.STATUS_STREAM), (
                            status, body[:200])
                        if body:
                            arrs = ws.decode_arrays(body)
                            if arrs and arrs[0].size:
                                chunks.append(arrs[0])
                        if status == ws.STATUS_OK:
                            break
                streams[i] = np.concatenate(chunks)
            except Exception as e:  # noqa: BLE001 - joined and re-raised below
                errs.append(e)

        try:
            warm = engine.stats()["compiles"]
            threads = [threading.Thread(target=stream, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            assert not any(t.is_alive() for t in threads), \
                "a decode stream did not finish"
            if errs:
                raise errs[0]
            after = engine.stats()
            for toks in streams:
                assert toks.size == new, toks
                assert ((toks >= 0) & (toks < vocab)).all(), toks
            assert after["compiles"] == warm, \
                f"{after['compiles'] - warm} decode program(s) compiled " \
                "after warm-up"
        finally:
            server.stop()
        # reported, not gated (ROADMAP Design 6): greedy tokens of a
        # stream decoded alone vs inside the concurrent batch
        equal = [reference_decode(model, prompts[i], new,
                                  max_seq_len=cfg["decode_max_seq"]).tolist()
                 == streams[i].tolist() for i in range(3)]
        return {"toy_width": cfg["decode"]["hidden"],
                "streams": len(prompts), "tokens_per_stream": new,
                "warmup_compiles": warm,
                "post_warmup_compiles": after["compiles"] - warm,
                "solo_vs_batch_greedy_bitwise_equal": all(equal),
                "solo_vs_batch_compared": len(equal)}

    # ------------------------------------------------------------ 5
    def phase_hybrid(self):
        from __graft_entry__ import dryrun_multichip

        dryrun_multichip(self.n, devices=self.devices)
        return {"devices": self.n}


def _versions():
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal: toy widths, Pallas interpreter; "
                         "prints \"dry_run\": true")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.dry_run:
        print(f"chip_smoke: no TPU (jax found {device}); nothing was run. "
              "--dry-run is the CPU rehearsal.", file=sys.stderr)
        return 2

    import paddle_tpu as paddle
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    if args.dry_run:
        # the interpreter is asked for, never inferred; the toy
        # sequences sit below the kernel's default dispatch threshold
        paddle.set_flags({"pallas_interpret": True,
                          "pallas_attention_min_seq": TOY["llama_seq"]})
    smoke = Smoke(args.dry_run)
    header = dict(_versions(), **device)
    header.update(local_devices=smoke.n, compile_cache_dir=cache_dir,
                  compile_cache_entries=len(os.listdir(cache_dir)))
    if args.dry_run:
        header["dry_run"] = True
    smoke.emit(header)

    phases = [(0, "run_check", smoke.phase_run_check),
              (1, "train_bert", smoke.phase_train),
              (2, "serve_bert", smoke.phase_serve),
              (3, "flash_kernels", smoke.phase_kernels),
              (4, "decode_engine_toy", smoke.phase_decode)]
    if smoke.n >= 4:
        phases.append((5, "hybrid_axes", smoke.phase_hybrid))
    for idx, name, fn in phases:
        smoke.run_phase(idx, name, fn)
    smoke.scratch.cleanup()

    raised = smoke.runtime_warnings_from_paddle_tpu()
    result = {"ok": not smoke.failed and not raised, "device": device}
    if smoke.failed:
        result["failed_phases"] = smoke.failed
    if raised:
        result["runtime_warnings_from_paddle_tpu"] = raised
    if args.dry_run:
        result["dry_run"] = True
    smoke.emit(result)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
