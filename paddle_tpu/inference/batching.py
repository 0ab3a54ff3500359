"""Dynamic-batching serving engine: coalesce concurrent infer requests
into padded shape-bucket batches over one compiled-program cache.

On TPU, serving throughput comes almost entirely from batch parallelism
and from amortizing XLA compilation over stable shapes — a
thread-per-request predictor pays full dispatch per sample and a full
compile per novel shape. This engine is the runtime complement to
tracelint's static recompilation-hazard passes (TPU101-TPU104):

  requests --> bounded queue --> scheduler thread --> padded bucket batch
                (load shed,       (fire on max_batch_size                 \
                 deadline purge)   or max_wait_ms)                         --> per-bucket
                                        ^                                      AOT-compiled
  response <-- slice rows off <---------|--------------------------------- program
                                   watchdog thread
                              (heartbeat check, restart)

Shape buckets are powers of two (clamped to ``max_batch_size``): padding
the coalesced row count up to the next bucket means each bucket's
program compiles exactly once, no matter what request mix arrives.
Declared buckets are precompiled by :meth:`BatchingEngine.warmup` so the
first real request never eats a compile. The bounded queue plus
:class:`EngineOverloaded` (wire status ``2``) turn saturation into fast
rejection — load shedding — instead of unbounded memory growth.

Graceful degradation (at production scale, *recovering* from component
failure — not avoiding it — is what preserves throughput):

- **Scheduler watchdog**: the scheduler bumps a heartbeat each loop; a
  watchdog thread restarts a dead or wedged scheduler, failing only the
  in-flight group with a retryable status (:class:`SchedulerRestarted`,
  wire status 2) — parked requests are served by the restarted
  scheduler, never stranded.
- **Poisoned-bucket quarantine**: N consecutive compile/execute failures
  for one (bucket, signature) trip a circuit breaker — that bucket sheds
  fast (:class:`BucketQuarantined`, wire status 2) while other buckets
  keep serving; after a cooldown one half-open probe group re-admits it.
- **Deadlines**: a request may carry an absolute deadline; expired
  requests are purged *before* dispatch (no compute for a client that
  already gave up) and a group never waits past the tightest deadline of
  its members.
- **Chaos sites**: ``serving.scheduler.loop``, ``serving.compile[.bucketN]``,
  ``serving.execute[.bucketN]`` and ``serving.submit`` let the
  deterministic chaos harness (resilience/chaos.py) inject scheduler
  death, poisoned buckets, and mid-batch failures in CI (the artifact
  store adds ``artifact.get`` / ``artifact.verify`` / ``artifact.put``
  / ``artifact.put.publish``).
- **Sharded serving** (inference/sharding.py, opt-in via
  ``mesh="tp2"`` / ``PADDLE_TPU_SERVING_MESH``): weights commit to a
  device mesh once at load and every bucket program becomes a
  per-(bucket, mesh) pjit program — models bigger than one chip's HBM
  serve behind the same engine, wire-transparently (README "Sharded
  serving" has the determinism contract per mesh).
- **Persistent artifact store** (serialize/artifact_store.py, opt-in
  via ``PADDLE_TPU_ARTIFACT_DIR``): warmup and cold buckets consult a
  crash-safe on-disk store of exported programs before compiling —
  a fresh replica, hot reload, or restart warms its whole bucket
  ladder with zero XLA compiles, and any corrupt/torn/skewed artifact
  degrades to the inline compile it would have done anyway. Warmup is
  single-flight across processes: N replicas warming one bucket pay
  ONE compile fleet-wide.

Telemetry (paddle_tpu/obs): the engine's counters are obs.metrics
instruments — cmd-5 ``stats`` and cmd-3 ``health`` are consistent views
over them (read under one engine-lock acquisition) and the process
registry exposes the same instruments to Prometheus (wire cmd 6 and
``serve_model(metrics_port=)``). Per-request spans cover
enqueue -> batch -> (compile) -> execute, tagged with the
wire-propagated trace id (``infer(trace_id=...)``), and every AOT
bucket compile lands in the compile ledger (``obs.LEDGER``) with its
cost-analysis FLOPs and structural HLO fingerprint.

Env knobs (constructor kwargs override):
    PADDLE_TPU_SERVING_BREAKER_THRESHOLD   consecutive failures to trip
                                           a bucket breaker (default 3;
                                           0 disables the breaker)
    PADDLE_TPU_SERVING_BREAKER_COOLDOWN    seconds an open breaker waits
                                           before its half-open probe
                                           (default 5.0)
    PADDLE_TPU_SERVING_WATCHDOG_INTERVAL   heartbeat check period
                                           (default 0.5; 0 disables the
                                           watchdog)
    PADDLE_TPU_SERVING_WEDGE_TIMEOUT       heartbeat staleness (with work
                                           pending) treated as a wedged
                                           scheduler (default 30.0)

Determinism contract (verified in tests/test_serving_batching.py):
engine outputs are bitwise identical to unbatched ``Predictor.run`` for
any request of >= 2 rows and for all integer dtypes — padding rows are
sliced off before anything is returned, and XLA's row-independent
programs are bitwise row-stable across batch sizes >= 2 on CPU. The one
carve-out: XLA lowers batch-1 float matmuls to a gemv with different
rounding than the gemm used for batch >= 2, so a COALESCED 1-row float
request can differ from its solo baseline in the last ulp (a solo 1-row
request fires at bucket 1 — the same program as the baseline — and stays
bitwise equal). A 1-row tail chunk of a split oversized request pads to
bucket 2 for the same reason: its rows came from a >= 2-row baseline
dispatch, so it must stay in the gemm regime.
"""
import json
import os
import threading
import time
import traceback
import warnings
import weakref

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import tracing as obs_tracing
from ..obs.ledger import LEDGER
from ..resilience import chaos
from ..resilience.retry import _env_float, _env_int
from ..serialize import artifact_store as _artifacts
from ..serialize.export import deserialize_exported, serialize_exported
from . import sharding as _sharding

# Machine-checked lock order (tools/tracelint.py --concurrency, TPU309):
# the engine lock is the SUBSYSTEM lock; obs instrument and registry
# locks nest strictly inside it, never the reverse — otherwise metrics
# exposition could deadlock the serving hot path. These declarations
# turn the prose invariant from obs/metrics.py's docstring into a
# gated check.
# tpu-lock-order: BatchingEngine._lock < Metric._lock  # subsystem -> instrument
# tpu-lock-order: BatchingEngine._lock < Registry._lock  # collectors run OUTSIDE the registry lock

# Wire status byte for a shed request, from the machine-readable
# protocol spec (wire_spec is import-light: the engine still has no
# import-time dependency on the server).
from .wire_spec import STATUS_RETRYABLE as OVERLOADED_STATUS  # noqa: E402


class RetryableError(RuntimeError):
    """Transient serving failure: the caller should back off and retry
    (the server maps every subclass to wire status 2)."""

    status_code = OVERLOADED_STATUS


class EngineOverloaded(RetryableError):
    """Raised by submit/infer when the bounded queue is full: the caller
    should back off (the server maps this to wire status 2)."""


class SchedulerRestarted(RetryableError):
    """The scheduler died or wedged while this request's group was in
    flight; the watchdog restarted it. A dead scheduler never delivered
    the group's results; a wedged one may still be executing it — either
    way the results are discarded, never delivered, so retrying cannot
    observe a double answer (a wedge-triggered retry can, however,
    re-run rows the stuck execute eventually finishes — inference is
    side-effect free, so duplicate compute, not duplicate effects)."""


class BucketQuarantined(RetryableError):
    """This request's (bucket, signature) breaker is open after repeated
    compile/execute failures; the bucket sheds fast while it cools down.
    Other buckets keep serving."""


class DeadlineExceeded(RetryableError):
    """The request's deadline passed before its batch dispatched; it was
    dropped without spending compute (the client already gave up)."""


class EngineClosed(RuntimeError):
    pass


def bucket_rows(n, max_batch_size):
    """Next power-of-2 >= n, clamped to max_batch_size."""
    if n <= 0:
        raise ValueError(f"need at least one row, got {n}")
    if n >= max_batch_size:
        return max_batch_size
    return min(max_batch_size, 1 << (n - 1).bit_length())


def _signature(arrays):
    """Batch-compatibility key: dtype + trailing dims of every input
    (requests coalesce only when everything but the row count matches)."""
    return tuple((a.dtype.str, a.shape[1:]) for a in arrays)


class _Request:
    __slots__ = ("inputs", "rows", "sig", "event", "outputs", "error",
                 "t_enqueue", "min_bucket", "deadline", "trace_id")

    def __init__(self, inputs, rows, sig, min_bucket=1, deadline=None,
                 trace_id=None):
        self.inputs = inputs
        self.rows = rows
        self.sig = sig
        self.event = threading.Event()
        self.outputs = None
        self.error = None
        self.t_enqueue = time.monotonic()
        # split chunks of a >= 2-row request carry min_bucket=2: a solo
        # 1-row tail chunk must still fire in the batch >= 2 regime
        # (bucket 1 is XLA's gemv regime, which rounds differently) to
        # keep the split path bitwise equal to the unbatched baseline
        self.min_bucket = min_bucket
        # absolute time.monotonic() drop-dead point (None = no deadline)
        self.deadline = deadline
        # wire-propagated trace id (obs.tracing): spans recorded for
        # this request's enqueue/execute carry it
        self.trace_id = trace_id

    def fail(self, error):
        """Deliver an error result unless a result already landed."""
        if not self.event.is_set():
            self.error = error
            self.event.set()


class _BucketStats:
    __slots__ = ("compiles", "store_loads", "batches", "requests", "rows",
                 "padded_rows", "total_ms", "max_ms")

    def __init__(self):
        self.compiles = 0  # real inline XLA compiles only
        self.store_loads = 0  # programs deserialized from the artifact
        # store — split so a store miss can never masquerade as (or
        # hide) a real recompile regression in cmd-5 stats
        self.batches = 0
        self.requests = 0
        self.rows = 0
        self.padded_rows = 0
        self.total_ms = 0.0
        self.max_ms = 0.0

    def as_dict(self):
        return {
            "compiles": self.compiles,
            "store_loads": self.store_loads,
            "batches": self.batches,
            "requests": self.requests,
            "rows": self.rows,
            "padded_rows": self.padded_rows,
            "total_ms": round(self.total_ms, 3),
            "avg_ms": round(self.total_ms / self.batches, 3)
                      if self.batches else 0.0,
            "max_ms": round(self.max_ms, 3),
        }


class _Breaker:
    """Per-(bucket, signature) circuit breaker. All methods are called
    under the engine lock.

    closed --N consecutive failures--> open --cooldown--> half_open
      ^                                 ^                    |
      +------- probe succeeds ----------+--- probe fails ----+
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"
    __slots__ = ("threshold", "cooldown", "state", "failures", "opened_at",
                 "trips", "shed")

    def __init__(self, threshold, cooldown):
        self.threshold = threshold
        self.cooldown = cooldown
        self.state = self.CLOSED
        self.failures = 0
        self.opened_at = 0.0
        self.trips = 0
        self.shed = 0

    def allow(self, now):
        """May a group for this bucket dispatch now? OPEN past its
        cooldown admits exactly one probe (HALF_OPEN); a second group
        while the probe is in flight is shed."""
        if self.state == self.CLOSED:
            return True
        if self.state == self.OPEN and now - self.opened_at >= self.cooldown:
            self.state = self.HALF_OPEN
            return True
        return False

    # tpu-resource: releases=breaker
    def record_success(self):
        self.failures = 0
        self.state = self.CLOSED

    # tpu-resource: acquires=breaker
    def record_failure(self, now):
        self.failures += 1
        if self.threshold <= 0:
            return  # breaker disabled: count but never trip
        if self.state == self.HALF_OPEN or self.failures >= self.threshold:
            self.state = self.OPEN
            self.opened_at = now
            self.trips += 1

    def as_dict(self):
        return {"state": self.state, "consecutive_failures": self.failures,
                "trips": self.trips, "shed": self.shed}


# tpu-resource: releases=flight_lock
def _publish_in_background(store, key, lock, blob):
    """Publish off the hot path: the requester already has its
    program and the bytes are already serialized — only the store
    I/O runs on a daemon thread, so no request waits on disk. The
    single-flight lock is held until the publish lands (released
    in all cases — a crashed publisher's lock is reclaimed by
    peers via the staleness takeover)."""
    def work():
        try:
            store.put(key, blob)
        finally:
            store.release(lock)

    threading.Thread(target=work, name="artifact-publish",
                     daemon=True).start()


def store_backed_compile(store, key, inline_fn, export_and_run,
                         run_from_payload, warming=False,
                         warmup_wait_s=120.0):
    """The ONE store-consult-or-compile flow, shared by the batching
    engine's :class:`AotLayerRunner` and the decode engine's program
    cache (inference/decode.py). Returns ``(run, source)`` where
    ``source`` is ``"store"`` (deserialized from the artifact store)
    or ``"inline"`` (compiled in this process).

    Caller-supplied callbacks own the program specifics:

    - ``inline_fn() -> run``: plain lower+compile (also the degrade
      path for every store failure mode);
    - ``export_and_run() -> (blob, run)``: ONE export (trace +
      StableHLO lower) serving both the published artifact and this
      process's own program — the fleet is byte-identical by
      construction, and the winner never traces twice;
    - ``run_from_payload(payload) -> run or None``: materialize a
      verified store payload (deserialize under THIS runtime, aval
      check, probe execute), quarantining + returning None when
      anything about it is off.

    ``warming``: warmup is where single-flight matters — N replicas
    warming the same key block briefly on one O_EXCL lock so exactly
    one pays the compile and the rest load its published artifact.
    The hot path never blocks on a peer: a cold key under live
    traffic compiles inline immediately (publishing in the background
    when it holds the lock)."""
    if store is None:
        return inline_fn(), "inline"
    lock = None
    if warming:
        # ONE counted lookup: acquire_or_wait reads the store itself
        # (a warm uncontended key resolves on the first acquire+read)
        # — a separate get() first would count every peer-published
        # key as a miss AND a hit, pinning the hit-ratio of a
        # perfectly warm store at 50%
        lock, payload = store.acquire_or_wait(key, timeout=warmup_wait_s)
    else:
        payload = store.get(key)
    if payload is not None:
        run = run_from_payload(payload)
        if run is not None:
            return run, "store"
        # the artifact was bad (now quarantined): try to claim the
        # compile so a good one replaces it
        lock = lock or store.try_acquire(key)
    elif not warming:
        lock = store.try_acquire(key)
    if lock is not None:
        # we own the fleet-wide compile for this key
        try:
            blob, run = export_and_run()
        except Exception:  # noqa: BLE001 - degrade to plain inline
            # export or probe failed (not every program exports):
            # free the peers NOW (they compile themselves instead of
            # waiting out the staleness horizon on a corpse), then
            # serve through the store-less path
            store.release(lock)
            return inline_fn(), "inline"
        if warming:
            # synchronous publish: peers blocked in acquire_or_wait
            # are waiting for exactly this artifact
            try:
                store.put(key, blob)
            finally:
                store.release(lock)
        else:
            _publish_in_background(store, key, lock, blob)
        return run, "inline"
    return inline_fn(), "inline"


class AotLayerRunner:
    """Execute batches for a jit-loaded :class:`TranslatedLayer` through
    per-bucket ahead-of-time compiled programs.

    The layer's exported StableHLO must be batch-polymorphic in dim 0 of
    every input (``jit.save`` with ``InputSpec([None, ...])``); each
    bucket is then lowered+compiled exactly once with the weights passed
    as runtime arguments (shared on device across buckets, never baked
    into the program) and the batch buffers donated.
    """

    def __init__(self, layer, donate=True, store=None, mesh=None):
        import jax

        self._jax = jax
        self._layer = layer
        self._donate = donate
        # serving mesh (inference/sharding.py): "single" runs the
        # pre-sharding path byte-for-byte; a sharded mesh commits the
        # resident weights to the device mesh ONCE here and every
        # bucket program compiles with those shardings as in_shardings
        # (weights stay runtime args shared across buckets). The
        # canonical descriptor rides in every ArtifactKey: a sharded
        # export can never satisfy a single-chip key or vice versa.
        self._mesh = _sharding.resolve(mesh)
        self.mesh_desc = self._mesh.descriptor
        self._sharded_state = None
        if not self._mesh.is_single:
            self._mesh.build()  # fail fast: not enough devices = here,
            # with the remedy named, never mid-request
        # persistent compiled-artifact store (serialize.artifact_store):
        # warmup and cold buckets consult it before compiling, and
        # inline compiles publish back so the NEXT process (a fresh
        # replica, a hot reload, a restart) pays zero cold compiles.
        # None + no env opt-in = store-less, the pre-store behaviour.
        self._store = store if store is not None \
            else _artifacts.default_store()
        self._fingerprint = getattr(layer, "_model_fingerprint", None)
        # serving quant mode the layer was jit-saved under (None = f32):
        # rides in every ArtifactKey (quantized programs are distinct
        # store identities), every ledger event, and the engine's
        # compile metrics — a mixed-precision fleet stays observable
        self.quant_mode = getattr(layer, "_quant_mode", None)
        self._warmup_wait_s = _env_float(
            "PADDLE_TPU_ARTIFACT_WARMUP_WAIT_S", 120.0)
        specs = getattr(layer, "_input_specs", None) or []
        if not specs:
            raise ValueError("layer has no input specs; was it jit-saved?")
        if not getattr(layer, "_polymorphic", False):
            raise ValueError(
                "dynamic batching needs a batch-polymorphic saved model: "
                "re-save with paddle.jit.save(..., input_spec="
                "[InputSpec([None, ...], dtype)]) so dim 0 exports as a "
                "symbolic size (BatchingEngine.for_callable is the "
                "fallback for fixed-shape models)")
        if not self._mesh.is_single:
            # shard once at load: these placed arrays are the runtime
            # args EVERY bucket program shares — per-device residency
            # is what makes a bigger-than-one-chip model servable
            params, p_sh = self._mesh.shard_arrays(
                [p._value for p in layer._parameters.values()])
            buffers, b_sh = self._mesh.shard_arrays(
                [jax.numpy.asarray(b)
                 for b in layer._loaded_buffers.values()])
            self._sharded_state = (params, p_sh, buffers, b_sh)
        self._trailing = []
        self._dtypes = []
        for shape, dtype in specs:
            if shape and shape[0] is not None:
                raise ValueError(
                    f"input spec {shape} has a concrete dim 0; every "
                    "input must be batch-polymorphic for bucket batching")
            if any(d is None for d in shape[1:]):
                raise ValueError(
                    f"input spec {shape} has a symbolic non-batch dim; "
                    "the batching engine buckets dim 0 only — re-save "
                    "with concrete trailing dims (or pad/bucket those "
                    "dims client-side before submitting)")
            self._trailing.append(tuple(int(d) for d in shape[1:]))
            self._dtypes.append(np.dtype(dtype))

    def default_signature(self):
        """The saved model's batch signature (for warmup)."""
        return tuple((dt.str, tr)
                     for dt, tr in zip(self._dtypes, self._trailing))

    # ------------------------------------------------- artifact store
    def _active_store(self):
        """The store to consult, or None. Needs a model fingerprint
        (jit.load computes one from the module bytes) and survives the
        operator kill switch (PADDLE_TPU_ARTIFACT_DISABLE wins even
        over an explicitly-passed store)."""
        if self._store is None or self._fingerprint is None:
            return None
        if _artifacts.disabled():
            return None
        return self._store

    def _artifact_key(self, bucket, sig):
        return _artifacts.ArtifactKey(self._fingerprint, bucket, sig,
                                      mesh=self.mesh_desc,
                                      quant=self.quant_mode)

    def _bucket_state(self, bucket, sig):
        """(flat_fn, param_arrays, buffer_arrays, specs, donate) for one
        bucket — shared by the inline compile and the export publish so
        the two can never drift (the published artifact IS the program
        the inline path would have compiled). Under a sharded mesh the
        param/buffer arrays are the mesh-committed residents and every
        spec carries its sharding, so the lowered program IS the
        sharded pjit program."""
        jax = self._jax
        layer = self._layer

        def flat_fn(param_list, buffer_list, *inputs):
            out = layer._call_fn(param_list, buffer_list, *inputs)
            return tuple(out) if isinstance(out, (tuple, list)) else (out,)

        if self._sharded_state is not None:
            param_arrays, p_sh, buffer_arrays, b_sh = self._sharded_state
            repl = self._mesh.replicated()
            param_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=s)
                           for a, s in zip(param_arrays, p_sh)]
            buffer_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=s)
                            for a, s in zip(buffer_arrays, b_sh)]
            in_specs = [jax.ShapeDtypeStruct((bucket,) + tr,
                                             np.dtype(dt), sharding=repl)
                        for dt, tr in sig]
        else:
            param_arrays = [p._value for p in layer._parameters.values()]
            buffer_arrays = [jax.numpy.asarray(b)
                             for b in layer._loaded_buffers.values()]
            param_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in param_arrays]
            buffer_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                            for a in buffer_arrays]
            in_specs = [jax.ShapeDtypeStruct((bucket,) + tr, np.dtype(dt))
                        for dt, tr in sig]
        donate = tuple(range(2, 2 + len(sig))) if self._donate else ()
        return (flat_fn, param_arrays, buffer_arrays,
                (param_specs, buffer_specs, in_specs), donate)

    def _jit(self, flat_fn, donate, n_inputs):
        """The one jit construction both the inline compile and the
        export share. Single mesh: byte-for-byte the historical call
        (no sharding kwargs). Sharded: weights pinned to their discipline
        layout, batch inputs and outputs replicated, so the host-side
        engine (and the wire) see exactly the single-chip shapes."""
        jax = self._jax
        if self._sharded_state is None:
            return jax.jit(flat_fn, donate_argnums=donate)
        _, p_sh, _, b_sh = self._sharded_state
        repl = self._mesh.replicated()
        return jax.jit(flat_fn, donate_argnums=donate,
                       in_shardings=(list(p_sh), list(b_sh),
                                     *([repl] * n_inputs)),
                       out_shardings=repl)

    def compile(self, bucket, sig, warming=False):
        """-> (run, source): the bucket's program, loaded from the
        artifact store (``source == "store"``) or compiled inline
        (``"inline"``). Every store failure mode — miss, corrupt,
        version skew, undeserializable, probe crash — degrades to the
        inline path; a store can make this slower than compiling only
        by the cost of one verified read.

        ``warming``: warmup is where single-flight matters — N replicas
        warming the same bucket ladder block briefly on one O_EXCL
        lock so exactly one pays the compile and the rest load its
        published artifact. The hot path never blocks on a peer: a
        cold bucket under live traffic compiles inline immediately
        (publishing in the background when it holds the lock)."""
        store = self._active_store()
        if store is None:
            return self._compile_inline(bucket, sig), "inline"
        key = self._artifact_key(bucket, sig)

        def export_and_run():
            # timed end to end (export trace/lower + probe compile):
            # this event is a real cold compile and must be comparable
            # to the store-less path's aot events. One _bucket_state
            # serves both steps — rebuilding it means re-wrapping
            # every param/buffer per cold bucket.
            t0 = time.monotonic()
            state = self._bucket_state(bucket, sig)
            exported = self._export(bucket, sig, state=state)
            blob = serialize_exported(exported)
            run = self._make_run(exported, bucket, sig, state=state)
            LEDGER.record(f"serving/bucket{bucket}",
                          duration_s=time.monotonic() - t0,
                          kind="aot",
                          extra={"bucket": bucket, "via": "export",
                                 "signature": [[dt, list(tr)]
                                               for dt, tr in sig],
                                 **self._quant_extra()})
            return blob, run

        return store_backed_compile(
            store, key,
            inline_fn=lambda: self._compile_inline(bucket, sig),
            export_and_run=export_and_run,
            run_from_payload=lambda payload: self._run_from_payload(
                store, key, payload, bucket, sig),
            warming=warming, warmup_wait_s=self._warmup_wait_s)

    def _make_run(self, exported, bucket, sig, state=None):
        """run callable over an exported module, gated by everything
        bytes alone cannot prove: its input avals match the params/
        buffers/bucket we will call it with, and a zero-batch probe
        executes (paying the XLA compile HERE, never on live traffic).
        Raises on any mismatch/failure — callers decide between
        quarantine (store loads) and inline fallback (own exports)."""
        (_, param_arrays, buffer_arrays,
         (param_specs, buffer_specs, in_specs), _) = \
            state if state is not None else self._bucket_state(bucket, sig)
        # mesh skew is a clean KEY miss in the normal flow; this gate
        # is the defense in depth (copied store dir, hand-loaded blob):
        # a program exported for N devices must never reach an engine
        # whose mesh expects M
        _sharding.check_nr_devices(
            exported, None if self._sharded_state is None else self._mesh)
        # canonicalize through jax's dtype rules (x64 disabled traces
        # i64/f64 specs as i32/f32): the EXPORTED avals are always
        # canonical, and the inline path canonicalizes identically at
        # lowering — the two must be compared in the same space
        canon = self._jax.dtypes.canonicalize_dtype
        expect = [(tuple(s.shape), np.dtype(canon(s.dtype)))
                  for s in (*param_specs, *buffer_specs, *in_specs)]
        got = [(tuple(a.shape), np.dtype(a.dtype))
               for a in exported.in_avals]
        if got != expect:
            raise ValueError(
                f"aval mismatch: artifact {got} vs expected {expect}")

        def run(batch_arrays):
            out = exported.call(param_arrays, buffer_arrays, *batch_arrays)
            return [np.asarray(o) for o in out]

        probe = [np.zeros((bucket,) + tuple(tr), np.dtype(dt))
                 for dt, tr in sig]
        outs = run(probe)
        for o in outs:
            if getattr(o, "ndim", 0) == 0 or o.shape[0] != bucket:
                raise ValueError(
                    f"probe output shape {getattr(o, 'shape', ())} "
                    f"does not keep the {bucket}-row batch dim")
        return run

    def _run_from_payload(self, store, key, payload, bucket, sig):
        """Materialize a store artifact into a run callable, or None
        (with the artifact quarantined) when anything about it is off.
        The payload already passed sha256 verification; _make_run
        checks the rest (deserializes under THIS runtime, aval match,
        probe execution) — so a store-loaded program can never first
        fail on live traffic."""
        t0 = time.monotonic()
        try:
            exported = deserialize_exported(payload)
            run = self._make_run(exported, bucket, sig)
        except Exception as e:  # noqa: BLE001 - any bad artifact degrades
            store.quarantine(key, str(e))
            return None
        # the ledger distinguishes store loads from real compiles, so
        # single-flight across processes is assertable ("exactly one
        # kind=aot event per bucket, fleet-wide") and a compile count
        # never conflates a store miss with a regression
        LEDGER.record(f"serving/bucket{bucket}",
                      duration_s=time.monotonic() - t0, kind="store",
                      extra={"bucket": bucket,
                             "artifact": key.digest(),
                             "signature": [[dt, list(tr)]
                                           for dt, tr in sig],
                             **self._quant_extra()})
        return run

    def _export(self, bucket, sig, state=None):
        """Export this bucket's program (the same flat_fn + specs +
        donation the inline compile uses) — ONE trace + lower that the
        publish path serializes and the winner's own run is built on."""
        from jax import export as jax_export

        flat_fn, _, _, (param_specs, buffer_specs, in_specs), donate = \
            state if state is not None else self._bucket_state(bucket, sig)
        with warnings.catch_warnings():
            # same carve-out as the inline compile: unused donations on
            # tiny models are an optimization miss, not noise-worthy
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jax_export.export(
                self._jit(flat_fn, donate, len(in_specs)))(
                    param_specs, buffer_specs, *in_specs)

    def _export_bytes(self, bucket, sig):
        """Serialized form of :meth:`_export` (the published payload)."""
        return serialize_exported(self._export(bucket, sig))

    def _quant_extra(self):
        """Ledger-event mode/mesh tags. Empty for f32/single, so every
        historical event shape stays byte-identical."""
        extra = {}
        if self.quant_mode:
            extra["quant"] = self.quant_mode
        if self.mesh_desc != _sharding.SINGLE:
            extra["mesh"] = self.mesh_desc
        return extra

    def store_stats(self):
        store = self._active_store()
        return store.stats() if store is not None else None

    # ---------------------------------------------------- inline compile
    def _compile_inline(self, bucket, sig):
        """Lower + compile the bucket's program. Called once per bucket
        by the engine's cache; the compiled callable takes the padded
        numpy batch arrays and returns a list of numpy outputs."""
        (flat_fn, param_arrays, buffer_arrays,
         (param_specs, buffer_specs, in_specs), donate) = \
            self._bucket_state(bucket, sig)
        t0 = time.monotonic()
        with warnings.catch_warnings():
            # tiny models may leave a donated batch buffer unused; that
            # is an optimization miss, not an error worth a warning per
            # compile
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = (self._jit(flat_fn, donate, len(in_specs))
                        .lower(param_specs, buffer_specs, *in_specs)
                        .compile())
        # every AOT compile lands in the process compile ledger: bucket,
        # duration, cost_analysis FLOPs/bytes, structural HLO
        # fingerprint
        LEDGER.record(f"serving/bucket{bucket}",
                      duration_s=time.monotonic() - t0, compiled=compiled,
                      kind="aot",
                      extra={"bucket": bucket,
                             "signature": [[dt, list(tr)]
                                           for dt, tr in sig],
                             **self._quant_extra()})

        def run(batch_arrays):
            out = compiled(param_arrays, buffer_arrays, *batch_arrays)
            # np.asarray is the device->host readback: the true sync
            # point (PERF.md), and the bytes the server will encode
            return [np.asarray(o) for o in out]

        return run

    def prime(self, run, bucket, sig):
        """No-op: compile() above already AOT-compiled the program."""


class CallableRunner:
    """Fallback runner wrapping any ``fn(*arrays) -> list[array]`` (e.g.
    a fixed-shape model or a plain python function). There is no AOT
    cache to manage — the bucket's real compile happens inside XLA's
    own jit cache on the first batch executed at that size, so
    ``warmup`` primes each bucket by running a zero batch through it."""

    def __init__(self, fn):
        self._fn = fn

    def default_signature(self):
        return None

    def compile(self, bucket, sig, warming=False):
        fn = self._fn

        def run(batch_arrays):
            out = fn(*batch_arrays)
            if not isinstance(out, (list, tuple)):
                out = [out]
            return [np.asarray(o._value if hasattr(o, "_value") else o)
                    for o in out]

        return run, "inline"

    def store_stats(self):
        return None

    def prime(self, run, bucket, sig):
        """Execute a zero batch so XLA traces+compiles this bucket now,
        not on the first real request."""
        run([np.zeros((bucket,) + tuple(tr), np.dtype(dt))
             for dt, tr in sig])


class BatchingEngine:
    """Shared dynamic-batching front end for a served model.

    ``infer(inputs)`` blocks the calling thread until its rows come back
    from a coalesced batch; any number of threads (server handlers,
    cloned predictors) may call it concurrently. Construction::

        engine = BatchingEngine.for_layer(layer, max_batch_size=32,
                                          max_wait_ms=2.0, max_queue=256)
        engine.warmup()            # precompile all power-of-2 buckets
        outs = engine.infer([x])   # x: [rows, ...]; rows <= max splits

    Knobs:
      max_batch_size  cap on coalesced rows per fired batch (the
                      Config.enable_tensorrt_engine(max_batch_size=...)
                      knob routes here on TPU)
      max_wait_ms     scheduler fires a partial batch once the oldest
                      pending request has waited this long
      max_queue       bounded pending-request cap; beyond it submit()
                      sheds with EngineOverloaded (wire status 2)
      breaker_threshold / breaker_cooldown
                      poisoned-bucket quarantine (see _Breaker); env
                      defaults PADDLE_TPU_SERVING_BREAKER_*
      watchdog_interval / wedge_timeout
                      scheduler self-healing cadence; env defaults
                      PADDLE_TPU_SERVING_WATCHDOG_INTERVAL / _WEDGE_TIMEOUT
    """

    def __init__(self, runner, max_batch_size=32, max_wait_ms=2.0,
                 max_queue=256, name="engine", breaker_threshold=None,
                 breaker_cooldown=None, watchdog_interval=None,
                 wedge_timeout=None, cold_compile_timeout=None):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self._runner = runner
        self.max_batch_size = int(max_batch_size)
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.max_queue = int(max_queue)
        self.name = name
        self.breaker_threshold = int(
            breaker_threshold if breaker_threshold is not None
            else _env_int("PADDLE_TPU_SERVING_BREAKER_THRESHOLD", 3))
        self.breaker_cooldown = float(
            breaker_cooldown if breaker_cooldown is not None
            else _env_float("PADDLE_TPU_SERVING_BREAKER_COOLDOWN", 5.0))
        self.watchdog_interval = float(
            watchdog_interval if watchdog_interval is not None
            else _env_float("PADDLE_TPU_SERVING_WATCHDOG_INTERVAL", 0.5))
        self.wedge_timeout = float(
            wedge_timeout if wedge_timeout is not None
            else _env_float("PADDLE_TPU_SERVING_WEDGE_TIMEOUT", 30.0))
        # a cold-bucket compile runs on its own thread, outside the
        # scheduler the watchdog heartbeats — bound it separately
        # (generous: XLA compiles legitimately take tens of seconds)
        # so a wedged compile fails its waiters retryably instead of
        # hanging them forever. Enforced by the watchdog; 0 disables.
        self.cold_compile_timeout = float(
            cold_compile_timeout if cold_compile_timeout is not None
            else _env_float("PADDLE_TPU_SERVING_COLD_COMPILE_TIMEOUT",
                            300.0))
        # old duck-typed runners (pre-artifact-store protocol) define
        # compile(bucket, sig) -> run; the current protocol is
        # compile(bucket, sig, warming=False) -> (run, source). Detect
        # once here so both keep working — the same tolerance health()
        # extends to runners without store_stats()
        try:
            import inspect

            ps = inspect.signature(runner.compile).parameters
            self._compile_takes_warming = (
                "warming" in ps
                or any(p.kind is p.VAR_KEYWORD for p in ps.values()))
        except (TypeError, ValueError):
            self._compile_takes_warming = True
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending = []  # FIFO of _Request
        self._cache = {}  # (bucket, sig) -> compiled run callable
        self._compiling = {}  # (bucket, sig) -> Event for in-flight compile
        self._bucket_stats = {}  # (bucket, sig) -> _BucketStats
        self._breakers = {}  # (bucket, sig) -> _Breaker
        self._deadline_seen = False  # any deadline-bearing submit yet?
        self._init_metrics()
        self._declared = []  # bucket row counts from warmup()
        self._cold_threads = []  # in-flight cold-bucket compile threads
        self._cold_seq = 0
        self._cold_inflight = {}  # token -> (group, t_start): groups in
        # cold-compile threads, invisible to the scheduler heartbeat —
        # the watchdog bounds these by cold_compile_timeout
        self._closed = False
        self._closed_ev = threading.Event()
        # --- scheduler self-healing state ---
        # generation token: a watchdog restart bumps it; a superseded
        # scheduler thread notices and exits instead of double-serving
        self._sched_gen = 0
        self._heartbeat = time.monotonic()  # bumped each scheduler loop
        self._inflight = {}  # gen -> group popped but not yet delivered
        self._watchdog = None  # before the scheduler starts: its crash
        self._scheduler = threading.Thread(  # handler reads it
            target=self._run_scheduler, args=(0,),
            name=f"{name}-scheduler", daemon=True)
        self._scheduler.start()
        if self.watchdog_interval > 0:
            self._watchdog = threading.Thread(target=self._run_watchdog,
                                              name=f"{name}-watchdog",
                                              daemon=True)
            self._watchdog.start()

    # -------------------------------------------------------- telemetry
    def _init_metrics(self):
        """Per-engine obs instruments (obs.metrics). These ARE the
        engine's counters — cmd-5 ``stats`` and cmd-3 ``health`` read
        them (under the engine lock, so a snapshot is never torn) and
        the process registry exposes them to Prometheus through a
        registered collector. Instruments are engine-owned (const label
        ``engine=<name>``) rather than global so every engine instance
        keeps an isolated view; the exposition merges same-name
        families across engines."""
        cl = {"engine": self.name}
        M = obs_metrics
        lat_buckets = M.log_buckets(0.0001, 4.0, 10)
        self._m_requests = M.Counter(
            "paddle_serving_requests_total",
            "Requests admitted to the batching engine", const_labels=cl)
        self._m_rows = M.Counter(
            "paddle_serving_rows_total",
            "Input rows admitted to the batching engine", const_labels=cl)
        self._m_shed = M.Counter(
            "paddle_serving_shed_total",
            "Requests shed (reason: queue_full | quarantine)",
            labelnames=("reason",), const_labels=cl)
        self._m_deadline = M.Counter(
            "paddle_serving_deadline_total",
            "Deadline outcomes (stage: expired = dropped pre-dispatch, "
            "zero compute; late = expired in flight, compute spent)",
            labelnames=("stage",), const_labels=cl)
        self._m_restarts = M.Counter(
            "paddle_serving_scheduler_restarts_total",
            "Watchdog scheduler restarts", const_labels=cl)
        # quant and mesh ride as const labels (properties of the served
        # model/engine, not of an individual compile): a mixed
        # precision-and-topology fleet shows per-mode, per-mesh
        # compile/store-load series on one dashboard
        quant = getattr(self._runner, "quant_mode", None) or "f32"
        mesh = getattr(self._runner, "mesh_desc", None) or _sharding.SINGLE
        self._m_compiles = M.Counter(
            "paddle_serving_compiles_total",
            "Bucket program materializations (source: inline = a real "
            "XLA compile; store = deserialized from the persistent "
            "artifact store; quant: the serving quantization mode; "
            "mesh: the serving mesh descriptor)",
            labelnames=("bucket", "source"),
            const_labels={**cl, "quant": quant, "mesh": mesh})
        self._m_batches = M.Counter(
            "paddle_serving_batches_total",
            "Batches executed", labelnames=("bucket",), const_labels=cl)
        self._m_batch_rows = M.Counter(
            "paddle_serving_batch_rows_total",
            "Real rows executed per bucket", labelnames=("bucket",),
            const_labels=cl)
        self._m_padded = M.Counter(
            "paddle_serving_padded_rows_total",
            "Padding rows executed per bucket", labelnames=("bucket",),
            const_labels=cl)
        self._m_queue_depth = M.Gauge(
            "paddle_serving_queue_depth",
            "Pending requests in the bounded queue", const_labels=cl)
        self._m_queue_wait = M.Histogram(
            "paddle_serving_queue_wait_seconds",
            "Enqueue-to-dispatch wait per request",
            const_labels=cl, buckets=lat_buckets)
        self._m_occupancy = M.Histogram(
            "paddle_serving_batch_occupancy",
            "Real rows / bucket size per executed batch",
            const_labels=cl,
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        self._m_exec = M.Histogram(
            "paddle_serving_batch_exec_seconds",
            "Batch execute duration", labelnames=("bucket",),
            const_labels=cl, buckets=lat_buckets)
        self._instruments = [
            self._m_requests, self._m_rows, self._m_shed,
            self._m_deadline, self._m_restarts, self._m_compiles,
            self._m_batches, self._m_batch_rows, self._m_padded,
            self._m_queue_depth, self._m_queue_wait, self._m_occupancy,
            self._m_exec]
        # weakref so a leaked (never-closed) engine can still be
        # garbage-collected; a dead ref returns None, which the
        # registry treats as "auto-unregister me"
        ref = weakref.ref(self)

        def _collector():
            eng = ref()
            return eng._collect_families() if eng is not None else None

        self._obs_collector = _collector
        obs_metrics.REGISTRY.register_collector(_collector)

    def _collect_families(self):
        # one engine-lock acquisition for the whole family set: the
        # exposition sees the same consistent view cmd-5 stats does
        with self._lock:
            self._m_queue_depth.set(len(self._pending))
            return [m.collect() for m in self._instruments]

    # ------------------------------------------------------- constructors
    @classmethod
    def for_layer(cls, layer, donate=True, artifact_store=None,
                  mesh=None, **kw):
        """Engine over a jit-loaded batch-polymorphic TranslatedLayer
        (per-bucket AOT compile, donation on the batch buffers).
        ``artifact_store``: a serialize.ArtifactStore for persistent
        cross-process program reuse (default: env-gated
        ``default_store()`` — PADDLE_TPU_ARTIFACT_DIR opts in).
        ``mesh``: a serving mesh descriptor (``"tp2"``,
        ``"fsdp2xtp2"``; default env ``PADDLE_TPU_SERVING_MESH``, else
        single-chip) — weights shard once at load and every bucket
        program becomes a per-(bucket, mesh) pjit program (README
        "Sharded serving")."""
        return cls(AotLayerRunner(layer, donate=donate,
                                  store=artifact_store, mesh=mesh), **kw)

    @classmethod
    def for_callable(cls, fn, **kw):
        """Engine over any ``fn(*arrays) -> outputs`` callable."""
        return cls(CallableRunner(fn), **kw)

    # ------------------------------------------------------------- submit
    def infer(self, inputs, timeout=None, deadline=None, trace_id=None):
        """Run one request (list of arrays sharing dim 0 = rows) through
        the engine; returns the list of output arrays for those rows.

        ``timeout`` bounds only this caller's wait; ``deadline`` (an
        absolute ``time.monotonic()`` point) is additionally honored by
        the scheduler: an expired request is purged before dispatch
        (DeadlineExceeded) and a group never waits past the tightest
        deadline of its members.

        ``trace_id`` (default: the thread's ambient obs.tracing id)
        tags the request's recorded spans — ``serving.request`` (this
        whole call), ``serving.queue`` (enqueue -> dispatch) and
        ``serving.execute`` (its batch) — so a wire-propagated id can
        be followed across threads.

        Requests larger than max_batch_size are split into chunks and
        re-joined (the split path); each chunk occupies its own queue
        slot so an oversized request cannot bypass the shed cap.
        """
        inputs = [np.ascontiguousarray(a) for a in inputs]
        if not inputs:
            raise ValueError("infer() needs at least one input array")
        rows = int(inputs[0].shape[0]) if inputs[0].ndim else 0
        if rows <= 0:
            raise ValueError("inputs must have a leading batch dim >= 1")
        for a in inputs:
            if a.ndim == 0 or a.shape[0] != rows:
                raise ValueError(
                    "all inputs of one request must share dim 0 "
                    f"(got {[tuple(x.shape) for x in inputs]})")
        if trace_id is None:
            trace_id = obs_tracing.current_trace_id()
        t0 = time.perf_counter()
        try:
            if deadline is not None and time.monotonic() >= deadline:
                self._m_deadline.inc(stage="expired")
                raise DeadlineExceeded(
                    f"{self.name}: deadline passed before submission")
            if rows > self.max_batch_size:
                out = self._infer_split(inputs, rows, timeout, deadline,
                                        trace_id)
            else:
                req = self._submit(inputs, rows, deadline, trace_id)
                out = self._wait(req, timeout)
        except BaseException as e:
            self._span_request(trace_id, t0, rows, type(e).__name__)
            raise
        self._span_request(trace_id, t0, rows, "ok")
        return out

    def _span_request(self, trace_id, t0, rows, outcome):
        """End-of-request telemetry: aggregate always; a full span
        record only for traced requests (the bounded span buffer is a
        debugging surface, not a per-request firehose)."""
        dt = time.perf_counter() - t0
        if trace_id is not None:
            obs_tracing.record_span("serving.request", dt,
                                    trace_id=trace_id, engine=self.name,
                                    rows=rows, outcome=outcome)
        else:
            obs_tracing.observe("serving.request", dt)

    def _infer_split(self, inputs, rows, timeout, deadline, trace_id):
        n_chunks = -(-rows // self.max_batch_size)
        if n_chunks > self.max_queue:
            # a deterministic can-never-fit request must get a permanent
            # error, not EngineOverloaded: status 2 tells clients to back
            # off and RETRY, and this retry can never succeed
            raise ValueError(
                f"request of {rows} rows needs {n_chunks} chunks of "
                f"max_batch_size={self.max_batch_size} but the queue cap "
                f"is {self.max_queue}: split the request client-side or "
                "raise max_queue/max_batch_size")
        chunks = []
        for lo in range(0, rows, self.max_batch_size):
            hi = min(rows, lo + self.max_batch_size)
            chunks.append([a[lo:hi] for a in inputs])
        # all chunks are enqueued atomically: a partially-admitted
        # oversized request would compute rows only to discard them
        # when a later chunk sheds. One shared deadline covers them all
        # (the tightest deadline in any group a chunk joins).
        reqs = self._submit_chunks(
            chunks, min_bucket=min(2, self.max_batch_size),
            deadline=deadline, trace_id=trace_id)
        wait_until = (None if timeout is None
                      else time.monotonic() + timeout)
        parts = []
        for i, r in enumerate(reqs):
            left = (None if wait_until is None
                    else max(0.0, wait_until - time.monotonic()))
            try:
                parts.append(self._wait(r, left))
            except BaseException as e:
                # the joined result can never be produced now: pull the
                # sibling chunks still queued (freeing their shed-cap
                # slots) and fail the rest, or they fire full padded
                # batches nobody will ever read
                with self._cond:
                    for rest in reqs[i + 1:]:
                        try:
                            self._pending.remove(rest)
                        except ValueError:
                            pass  # already grouped/in flight; discarded
                for rest in reqs[i + 1:]:
                    rest.fail(e)
                raise
        return [np.concatenate([p[i] for p in parts])
                for i in range(len(parts[0]))]

    def _submit(self, inputs, rows, deadline=None, trace_id=None):
        return self._submit_chunks([inputs], deadline=deadline,
                                   trace_id=trace_id)[0]

    def _submit_chunks(self, chunks, min_bucket=1, deadline=None,
                       trace_id=None):
        """Admit every chunk or none (one queue slot per chunk, so an
        oversized request still counts fully against the shed cap)."""
        chaos.hit("serving.submit")
        with self._cond:
            if self._closed:
                raise EngineClosed(f"{self.name} is closed")
            if len(self._pending) + len(chunks) > self.max_queue:
                self._m_shed.inc(reason="queue_full")
                raise EngineOverloaded(
                    f"{self.name} queue full ({len(self._pending)} pending,"
                    f" cap {self.max_queue}, need {len(chunks)} slots); "
                    "request shed")
            reqs = []
            if deadline is not None:
                self._deadline_seen = True
            for chunk in chunks:
                rows = int(chunk[0].shape[0])
                req = _Request(chunk, rows, _signature(chunk), min_bucket,
                               deadline, trace_id)
                self._pending.append(req)
                self._m_requests.inc()
                self._m_rows.inc(rows)
                reqs.append(req)
            self._cond.notify_all()
        return reqs

    def _wait(self, req, timeout):
        if req.deadline is not None:
            # the scheduler purges expired requests (DeadlineExceeded);
            # the small grace lets that cleaner error win over a bare
            # TimeoutError when both fire together
            dl_left = max(0.0, req.deadline - time.monotonic()) + 0.25
            timeout = dl_left if timeout is None else min(timeout, dl_left)
        if not req.event.wait(timeout):
            # abandon: pull it out of the queue so the scheduler never
            # spends a batch slot computing rows nobody will read
            with self._cond:
                try:
                    self._pending.remove(req)
                except ValueError:
                    pass  # already grouped/in flight; result is discarded
            if (req.deadline is not None
                    and time.monotonic() >= req.deadline):
                # separate counter: deadline_expired promises "dropped
                # BEFORE dispatch, no compute spent" — an in-flight
                # expiry may have burned a full batch, and lumping it
                # in would skew the metric operators size budgets by
                self._m_deadline.inc(stage="late")
                raise DeadlineExceeded(
                    f"{self.name}: deadline passed while the request was "
                    "in flight; the result (if any) was discarded")
            raise TimeoutError("engine did not answer within timeout")
        if req.error is not None:
            raise req.error
        return req.outputs

    # ---------------------------------------------------------- scheduler
    def _run_scheduler(self, gen):
        try:
            self._scheduler_loop(gen)
        except Exception:  # noqa: BLE001 - watchdog owns recovery
            # The loop itself broke (injected chaos, unexpected bug).
            # Log it — a scheduler that vanishes without a traceback is
            # undebuggable — then die WITHOUT clearing _inflight: the
            # watchdog fails that group with a retryable status and
            # starts a replacement scheduler for the parked requests.
            traceback.print_exc()
            if self._watchdog is None:
                # watchdog disabled (interval 0): nobody else will
                # recover, so self-heal inline — the crash must never
                # strand the in-flight group or the parked queue
                self._restart_scheduler(gen, "died (watchdog disabled)")

    def _scheduler_loop(self, gen):
        while True:
            # unguarded on purpose: a single f64 store is GIL-atomic, the
            # value is monotonic, and the watchdog only compares it to a
            # staleness threshold — a lock here would put one acquisition
            # on every scheduler iteration for no correctness gain
            self._heartbeat = time.monotonic()  # tpu-lint: disable=TPU305  # benign race: GIL-atomic monotonic bump
            group = self._next_group(gen)
            if group is None:
                return  # closed and drained, or superseded by a restart
            # one region span an iteration, from the group's pop to its
            # hand-off: the gap between two of them is the scheduler
            # waiting for requests in _next_group
            with obs_tracing.span("serving.scheduler.loop",
                                  engine=self.name,
                                  rows=sum(r.rows for r in group)):
                with self._lock:
                    self._inflight[gen] = group
                # From here until dispatch hand-off, an unhandled exception
                # (e.g. injected chaos) kills this thread WITH the group
                # still recorded in _inflight — the watchdog then fails
                # exactly that group with a retryable status (never a hang)
                # and restarts the scheduler for the parked requests.
                chaos.hit("serving.scheduler.loop")
                bucket = self._group_bucket(group)
                key = (bucket, group[0].sig)
                now = time.monotonic()
                with self._lock:
                    br = self._breaker_for(key)
                    allowed = br.allow(now)
                    if not allowed:
                        br.shed += len(group)
                        self._m_shed.inc(len(group), reason="quarantine")
                if not allowed:
                    err = BucketQuarantined(
                        f"{self.name} bucket {bucket} is quarantined after "
                        f"{br.failures} consecutive failures; retry after "
                        f"cooldown ({self.breaker_cooldown}s)")
                    for r in group:
                        r.fail(err)
                    with self._lock:
                        self._inflight.pop(gen, None)
                    continue
                with self._lock:
                    cold = key not in self._cache
                if cold:
                    # a cold bucket pays a multi-second XLA compile: run it
                    # on its own thread so already-compiled buckets keep
                    # flowing instead of stalling head-of-line behind it.
                    # The cold thread owns delivery from here (its guarded
                    # wrapper cannot strand waiters).
                    with self._lock:
                        self._cold_seq += 1
                        token = self._cold_seq
                    t = threading.Thread(target=self._run_cold_group,
                                         args=(token, group, br),
                                         name=f"{self.name}-cold-compile",
                                         daemon=True)
                    with self._lock:
                        self._inflight.pop(gen, None)
                        self._cold_inflight[token] = (group, time.monotonic())
                        self._cold_threads = [x for x in self._cold_threads
                                              if x.is_alive()]
                        self._cold_threads.append(t)
                    t.start()
                else:
                    try:
                        self._run_group_guarded(group, br)
                    finally:
                        # _run_group_guarded never raises (it fails the
                        # group instead), so waiters are already answered —
                        # clear even on a BaseException so a later watchdog
                        # restart cannot double-fail a delivered group
                        with self._lock:
                            self._inflight.pop(gen, None)

    def _run_cold_group(self, token, group, br):
        """Like _run_group_guarded, but the breaker outcome is recorded
        only while this group still owns its cold-inflight token: once
        the watchdog timed the group out it already recorded a failure
        for this incident — the zombie thread's eventual outcome must
        not count the same incident twice, and a late zombie success
        must not flip an OPEN breaker straight past its cooldown."""
        try:
            self._run_group(group)
        except Exception as e:  # noqa: BLE001 - fail the group only
            now = time.monotonic()
            with self._lock:
                owned = self._cold_inflight.pop(token, None) is not None
                if br is not None and owned:
                    br.record_failure(now)
            for r in group:
                r.fail(e)
        else:
            with self._lock:
                owned = self._cold_inflight.pop(token, None) is not None
                if br is not None and owned:
                    br.record_success()

    def _run_group_guarded(self, group, br=None):
        try:
            self._run_group(group)
        except Exception as e:  # noqa: BLE001 - fail the group only
            now = time.monotonic()
            with self._lock:
                if br is not None:
                    br.record_failure(now)
            for r in group:
                r.fail(e)
        else:
            with self._lock:
                if br is not None:
                    br.record_success()

    def _purge_expired_locked(self, now):
        """Drop pending requests whose deadline already passed — before
        dispatch, so no compute is spent on a client that gave up.
        Called with the lock held."""
        if not self._deadline_seen:
            # deadline-free deployments skip the per-iteration O(queue)
            # scan entirely (sticky flag: set on the first deadline-
            # bearing submit, never cleared)
            return
        expired = [r for r in self._pending
                   if r.deadline is not None and now >= r.deadline]
        if not expired:
            return
        for r in expired:
            self._pending.remove(r)
            self._m_deadline.inc(stage="expired")
        err = DeadlineExceeded(
            f"{self.name}: deadline passed while queued; request dropped "
            "before dispatch")
        for r in expired:
            r.fail(err)

    def _next_group(self, gen):
        """Block until a same-signature group is ready to fire: either
        max_batch_size rows are pending or the oldest request has waited
        max_wait_ms — or the tightest deadline in the candidate group is
        about to pass. Returns the popped group (None = engine closed or
        this scheduler generation superseded)."""
        with self._cond:
            while True:
                if self._sched_gen != gen:
                    return None  # a watchdog restart superseded us
                now = time.monotonic()
                self._purge_expired_locked(now)
                if not self._pending:
                    if self._closed:
                        return None
                    # every producer of work notifies: submit, close and
                    # restart all notify_all under this same condition —
                    # an idle scheduler parked here is woken by ANY
                    # state change it could act on
                    self._cond.wait()  # tpu-lint: disable=TPU303  # all three wake sources notify_all under _cond
                    continue
                head = self._pending[0]
                group, rows = [], 0
                for r in self._pending:
                    if r.sig != head.sig:
                        continue
                    if rows + r.rows > self.max_batch_size:
                        break
                    group.append(r)
                    rows += r.rows
                deadline = head.t_enqueue + self.max_wait_s
                tight = min((r.deadline for r in group
                             if r.deadline is not None), default=None)
                if tight is not None:
                    # never coalesce-wait past the tightest deadline of
                    # the group's members; the 5ms margin dispatches the
                    # group BEFORE that deadline (the purge above would
                    # otherwise drop the request at the exact instant
                    # its group was due to fire)
                    deadline = min(deadline, tight - 0.005)
                if (rows >= self.max_batch_size or now >= deadline
                        or self._closed):
                    t_pop = time.monotonic()
                    for r in group:
                        self._pending.remove(r)
                        wait = t_pop - r.t_enqueue
                        self._m_queue_wait.observe(wait)
                        if r.trace_id is not None:
                            obs_tracing.record_span(
                                "serving.queue", wait,
                                trace_id=r.trace_id, engine=self.name,
                                rows=r.rows)
                    return group
                self._cond.wait(deadline - now)

    def _group_bucket(self, group):
        """Bucket for a popped group: next power of two over the
        coalesced rows, floored by any chunk's min_bucket (a solo
        1-row split tail pads to bucket 2 to stay in the bitwise-stable
        batch >= 2 regime)."""
        want = max(sum(r.rows for r in group),
                   max(r.min_bucket for r in group))
        return bucket_rows(want, self.max_batch_size)

    def _run_group(self, group):
        rows = sum(r.rows for r in group)
        sig = group[0].sig
        bucket = self._group_bucket(group)
        run, _ = self._compiled(
            bucket, sig,
            trace_id=next((r.trace_id for r in group
                           if r.trace_id is not None), None))
        n_in = len(sig)
        batch = []
        for i in range(n_in):
            parts = [r.inputs[i] for r in group]
            if bucket > rows:
                pad_shape = (bucket - rows,) + parts[0].shape[1:]
                parts.append(np.zeros(pad_shape, parts[0].dtype))
            batch.append(np.concatenate(parts) if len(parts) > 1
                         else parts[0])
        chaos.hit("serving.execute")
        chaos.hit(f"serving.execute.bucket{bucket}")
        # one execute per group and one region span for it (a Span per
        # batch, never per request); every further traced request of the
        # group gets a copy with the shared duration under its own id
        tids = sorted({r.trace_id for r in group if r.trace_id is not None})
        with obs_tracing.span("serving.execute",
                              trace_id=tids[0] if tids else None,
                              engine=self.name, bucket=bucket,
                              rows=rows) as sp:
            outs = run(batch)
        dt_ms = sp.duration_s * 1000.0
        for tid in tids[1:]:
            obs_tracing.record_span(
                "serving.execute", sp.duration_s, trace_id=tid,
                parent_id=sp.span_id, engine=self.name, bucket=bucket,
                rows=rows)
        for j, o in enumerate(outs):
            if getattr(o, "ndim", 0) == 0 or o.shape[0] != bucket:
                raise ValueError(
                    f"output {j} has shape {tuple(getattr(o, 'shape', ()))}"
                    f" but the batch has {bucket} rows: every output must "
                    "keep the batch dim as dim 0 so per-request rows can "
                    "be sliced back — batch-reduced outputs cannot go "
                    "through the batching engine")
        off = 0
        for r in group:
            r.outputs = [o[off:off + r.rows] for o in outs]
            off += r.rows
            r.event.set()
        with self._lock:
            st = self._stats_for(bucket, sig)
            st.batches += 1
            st.requests += len(group)
            st.rows += rows
            st.padded_rows += bucket - rows
            st.total_ms += dt_ms
            st.max_ms = max(st.max_ms, dt_ms)
            bs = str(bucket)
            self._m_batches.inc(bucket=bs)
            self._m_batch_rows.inc(rows, bucket=bs)
            self._m_padded.inc(bucket - rows, bucket=bs)
            self._m_exec.observe(dt_ms / 1000.0, bucket=bs)
            self._m_occupancy.observe(rows / bucket)

    # ----------------------------------------------------------- watchdog
    def _run_watchdog(self):
        """Restart a dead or wedged scheduler. Death (an unhandled
        exception escaped the loop — e.g. injected chaos) and wedging
        (heartbeat stale AND the oldest pending request stale, so a long
        legitimate execute with a fresh queue never false-positives) get
        the same treatment: bump the generation, fail only the in-flight
        group with a retryable status, start a fresh scheduler thread.
        Parked requests stay queued and are served by the new thread."""
        while not self._closed_ev.wait(self.watchdog_interval):
            with self._lock:
                if self._closed:
                    return
                gen = self._sched_gen
                th = self._scheduler
                hb = self._heartbeat
                head = self._pending[0] if self._pending else None
                group = self._inflight.get(gen)
            now = time.monotonic()
            dead = not th.is_alive()
            # staleness witness: the queue head, or — when the queue is
            # empty — the in-flight group itself (a scheduler wedged
            # mid-execute on the LAST request must still be caught, or
            # its waiters hang forever)
            if head is not None:
                oldest = head.t_enqueue
            elif group:
                oldest = min(r.t_enqueue for r in group)
            else:
                oldest = None
            wedged = (oldest is not None
                      and now - hb > self.wedge_timeout
                      and now - oldest > self.wedge_timeout)
            if dead:
                self._restart_scheduler(gen, "died")
            elif wedged:
                self._restart_scheduler(gen, "wedged (heartbeat stale)")
            self._fail_overdue_cold_groups(now)

    def _fail_overdue_cold_groups(self, now):
        """Cold-compile groups run outside the scheduler the heartbeat
        watches; bound them by cold_compile_timeout so a wedged XLA
        compile fails its waiters retryably instead of hanging them
        (and every later same-bucket group queued behind its in-flight
        compile event) forever. The zombie thread may still finish and
        cache its program — results go nowhere, r.fail is a no-op once
        delivery happened."""
        if self.cold_compile_timeout <= 0:
            return
        with self._lock:
            overdue = [(tok, grp)
                       for tok, (grp, t0) in self._cold_inflight.items()
                       if now - t0 > self.cold_compile_timeout]
            for tok, _ in overdue:
                self._cold_inflight.pop(tok, None)
        for _, grp in overdue:
            # count toward the bucket's breaker: a compile that keeps
            # wedging must quarantine the bucket (sheds happen BEFORE
            # cold dispatch), which bounds the stuck-thread population
            # at breaker_threshold instead of one per client retry
            key = (self._group_bucket(grp), grp[0].sig)
            with self._lock:
                self._breaker_for(key).record_failure(time.monotonic())
            err = RetryableError(
                f"{self.name}: cold bucket compile/execute exceeded "
                f"cold_compile_timeout={self.cold_compile_timeout}s; "
                "request failed retryable (the compile may still finish "
                "and cache its program for the next attempt)")
            for r in grp:
                r.fail(err)

    def _restart_scheduler(self, observed_gen, reason):
        with self._cond:
            if self._closed or observed_gen != self._sched_gen:
                return  # already restarted (or shutting down)
            self._sched_gen += 1
            gen = self._sched_gen
            stranded = self._inflight.pop(observed_gen, None)
            if stranded:
                # if the stranded group was a HALF_OPEN probe, count it
                # as a failed probe (back to OPEN, fresh cooldown) — the
                # probe's own record_failure may never run, and a
                # breaker stuck HALF_OPEN sheds its bucket forever. A
                # CLOSED breaker is left alone: a scheduler death is not
                # the bucket's fault.
                key = (self._group_bucket(stranded), stranded[0].sig)
                br = self._breakers.get(key)
                if br is not None and br.state == _Breaker.HALF_OPEN:
                    br.record_failure(time.monotonic())
            self._m_restarts.inc()
            self._heartbeat = time.monotonic()
            t = threading.Thread(target=self._run_scheduler, args=(gen,),
                                 name=f"{self.name}-scheduler-g{gen}",
                                 daemon=True)
            self._scheduler = t
            # start INSIDE the lock: a concurrent close() reading
            # self._scheduler must never join() a not-yet-started
            # thread (RuntimeError). The new thread just parks on this
            # same lock until we release it.
            t.start()  # tpu-lint: disable=TPU304  # load-bearing: close() must never join an unstarted thread
            self._cond.notify_all()  # a superseded thread parked in wait()
        if stranded:
            err = SchedulerRestarted(
                f"{self.name} scheduler {reason} and was restarted; this "
                "request's group was in flight — its results (if any) "
                "were discarded, never delivered — retry it")
            for r in stranded:
                r.fail(err)

    # ------------------------------------------------------ compiled cache
    def _stats_for(self, bucket, sig):
        key = (bucket, sig)
        st = self._bucket_stats.get(key)
        if st is None:
            st = self._bucket_stats[key] = _BucketStats()
        return st

    def _breaker_for(self, key):
        """Called with the lock held."""
        br = self._breakers.get(key)
        if br is None:
            br = self._breakers[key] = _Breaker(self.breaker_threshold,
                                               self.breaker_cooldown)
        return br

    def _compiled(self, bucket, sig, trace_id=None, warming=False):
        """Per-bucket compiled program; materializes exactly once per
        (bucket, signature) in-process — from the artifact store when
        one is attached and has a verified program (source "store"),
        inline otherwise (source "inline"). Returns (run, source)
        where source is None for an in-process cache hit. Compiles run
        outside the lock (XLA can take seconds; infer submissions must
        not block on them); an in-flight event per key makes racing
        callers (warmup thread, concurrent cold groups) WAIT for the
        one compile instead of burning CPU redoing it N times.
        ``warming`` flows to the runner: warmup may block on a peer
        replica's single-flight compile, the hot path never does.
        ``trace_id`` (a traced request in the group that pays the
        compile) tags the serving.compile span; warmup/untraced
        compiles only feed the summary table."""
        key = (bucket, sig)
        while True:
            with self._lock:
                run = self._cache.get(key)
                if run is not None:
                    return run, None
                ev = self._compiling.get(key)
                if ev is None:
                    ev = self._compiling[key] = threading.Event()
                    mine = True
                else:
                    mine = False
            if not mine:
                # loop: pick up the cached result, or take over as the
                # owner if the first compile failed. Bounded: if the
                # owner's compile wedges, each waiting cold thread must
                # fail its group and EXIT (unbounded ev.wait would leak
                # one permanently-blocked thread per client retry)
                limit = self.cold_compile_timeout
                if limit > 0 and not ev.wait(limit):
                    # retryable: the owner's compile may still land and
                    # cache the program for the caller's next attempt
                    raise RetryableError(
                        f"{self.name}: compile for bucket {bucket} "
                        f"still in flight after cold_compile_timeout="
                        f"{limit}s; retry later")
                elif limit <= 0:
                    # cold_compile_timeout=0 is the operator explicitly
                    # disabling the bound; honour it
                    ev.wait()  # tpu-lint: disable=TPU303  # unbounded wait is the documented timeout-disabled mode
                continue
            try:
                chaos.hit("serving.compile")
                chaos.hit(f"serving.compile.bucket{bucket}")
                with obs_tracing.span("serving.compile", trace_id=trace_id,
                                      engine=self.name,
                                      bucket=bucket) as sp:
                    if self._compile_takes_warming:
                        res = self._runner.compile(bucket, sig,
                                                   warming=warming)
                    else:
                        res = self._runner.compile(bucket, sig)
                    run, source = (res if isinstance(res, tuple)
                                   else (res, "inline"))
                    sp.attrs["source"] = source
            except BaseException:
                with self._lock:
                    self._compiling.pop(key, None)
                ev.set()
                raise
            with self._lock:
                self._cache[key] = run
                st = self._stats_for(bucket, sig)
                if source == "store":
                    st.store_loads += 1
                else:
                    st.compiles += 1
                self._m_compiles.inc(bucket=str(bucket), source=source)
                self._compiling.pop(key, None)
            ev.set()
            return run, source

    def warmup(self, buckets=None, signature=None):
        """Precompile bucket programs at server start so no request pays
        a compile. Default buckets: every power of two up to
        max_batch_size (plus max itself). Returns the declared list."""
        sig = signature or self._runner.default_signature()
        if sig is None:
            raise ValueError(
                "warmup needs a signature for a callable-backed engine: "
                "pass signature=[(dtype_str, trailing_shape), ...]")
        sig = tuple((np.dtype(dt).str, tuple(tr)) for dt, tr in sig)
        if buckets is None:
            buckets = []
            b = 1
            while b < self.max_batch_size:
                buckets.append(b)
                b <<= 1
            buckets.append(self.max_batch_size)
        buckets = sorted({bucket_rows(int(b), self.max_batch_size)
                          for b in buckets})
        for b in buckets:
            # warming=True: warmup is the single-flight window — N
            # replicas warming the same ladder against a shared
            # artifact store produce ONE compile per bucket (the rest
            # block briefly and load the winner's published program)
            run, source = self._compiled(b, sig, warming=True)
            if source is not None:
                # callable-backed runners compile lazily inside XLA's
                # jit cache: prime with a zero batch so the "no request
                # pays a compile" promise holds there too (no-op for
                # the AOT runner, whose compile() already compiled)
                self._runner.prime(run, b, sig)
        with self._lock:
            self._declared = buckets
        return buckets

    def declared_buckets(self):
        with self._lock:
            return list(self._declared)

    # -------------------------------------------------------------- stats
    def stats(self):
        """Snapshot of engine counters (the `stats` wire command).

        A *view over the obs registry*: every scalar here reads the
        same instruments the Prometheus exposition renders. The whole
        snapshot — registry-backed scalars AND per-bucket tables — is
        taken under one engine-lock acquisition, so a mid-update read
        can never return torn totals (e.g. ``rows`` bumped but
        ``padded`` not yet)."""
        with self._lock:
            buckets = {}
            for (bucket, sig), st in sorted(self._bucket_stats.items(),
                                            key=lambda kv: kv[0][0]):
                d = st.as_dict()
                d["signature"] = [[dt, list(tr)] for dt, tr in sig]
                br = self._breakers.get((bucket, sig))
                if br is not None:
                    d["breaker"] = br.as_dict()
                buckets.setdefault(str(bucket), []).append(d)
            states = [br.state for br in self._breakers.values()]
            return {
                "name": self.name,
                "quant": getattr(self._runner, "quant_mode", None) or "f32",
                "mesh": getattr(self._runner, "mesh_desc", None)
                        or _sharding.SINGLE,
                "max_batch_size": self.max_batch_size,
                "max_wait_ms": round(self.max_wait_s * 1000.0, 3),
                "max_queue": self.max_queue,
                "declared_buckets": list(self._declared),
                "queue_depth": len(self._pending),
                "requests": int(self._m_requests.value()),
                "rows": int(self._m_rows.value()),
                "shed_count": int(self._m_shed.value(reason="queue_full")),
                "quarantine_shed": int(
                    self._m_shed.value(reason="quarantine")),
                "deadline_expired": int(
                    self._m_deadline.value(stage="expired")),
                "deadline_late": int(self._m_deadline.value(stage="late")),
                "scheduler_restarts": int(self._m_restarts.value()),
                "breaker": {
                    "threshold": self.breaker_threshold,
                    "cooldown_s": self.breaker_cooldown,
                    "open": states.count(_Breaker.OPEN),
                    "half_open": states.count(_Breaker.HALF_OPEN),
                    "trips": sum(br.trips
                                 for br in self._breakers.values()),
                },
                "compiles": sum(st.compiles
                                for st in self._bucket_stats.values()),
                "store_loads": sum(st.store_loads
                                   for st in self._bucket_stats.values()),
                "buckets": buckets,
            }

    def stats_json(self):
        return json.dumps(self.stats())

    def health(self):
        """Liveness snapshot for the `health` wire command: is the
        scheduler alive, how stale is its heartbeat, which buckets are
        quarantined, how deep is the queue."""
        now = time.monotonic()
        # store stats walk the artifact directory (file I/O): taken
        # BEFORE the engine lock so a slow disk never stalls the
        # serving hot path behind a health probe (getattr: custom
        # duck-typed runners may predate store_stats)
        store_stats = getattr(self._runner, "store_stats", lambda: None)()
        with self._lock:
            alive = self._scheduler.is_alive()
            quarantined = sorted(
                bucket for (bucket, _sig), br in self._breakers.items()
                if br.state != _Breaker.CLOSED)
            return {
                "ok": alive and not self._closed,
                "closed": self._closed,
                "scheduler_alive": alive,
                "heartbeat_age_s": round(now - self._heartbeat, 3),
                "scheduler_restarts": int(self._m_restarts.value()),
                "queue_depth": len(self._pending),
                "quarantined_buckets": quarantined,
                "cold_compiles_inflight": len(self._cold_inflight),
                "declared_buckets": list(self._declared),
                "mesh": getattr(self._runner, "mesh_desc", None)
                        or _sharding.SINGLE,
                "artifact_store": store_stats,
            }

    # -------------------------------------------------------------- close
    def close(self, timeout=5.0):
        """Stop the scheduler; pending requests still fire (partial
        batches), new submissions raise EngineClosed."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._closed_ev.set()
            self._cond.notify_all()
            sched = self._scheduler
        obs_metrics.REGISTRY.unregister_collector(self._obs_collector)
        sched.join(timeout)
        if self._watchdog is not None:
            self._watchdog.join(timeout)
        with self._lock:
            colds = list(self._cold_threads)
            self._cold_threads = []
        for t in colds:
            t.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
