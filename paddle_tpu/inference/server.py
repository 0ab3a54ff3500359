"""Inference server: serves a saved model over the same length-prefixed
TCP framing as the PS service, so the C API (native/c_api.cc), Go/R
clients, or any socket speaker can run predictions against the TPU
process.

Reference: paddle/fluid/inference/capi/ + go/paddle/predictor.go talk to
an in-process C++ predictor; on TPU the predictor owns device state and
compiled programs, so out-of-process callers go through this service
instead (the architecture real TPU serving uses).

wire format (little-endian):
  request:  u32 body_len | u8 cmd | payload
  cmds: 1 infer  payload = u8 n_inputs, per input:
            u8 dtype (0=f32, 1=i32, 2=i64, 3=bool) | u8 ndim |
            i64 dims[ndim] | data
          ... optionally followed by trailing fields, each tagged by a
          marker byte and parseable in any order:
            u8 0xDD | f64 timeout_ms (relative budget; the server
            computes the absolute deadline at receipt and drops the
            request without dispatch once it expires)
            u8 0x1D | u64 trace_id (non-zero; tags the request's
            obs.tracing spans across enqueue/batch/execute/reply so
            one request can be followed through the engine)
            u8 0x7E | u64 tenant_id (inference.fleet.tenant_id(name);
            the fleet router keys admission control and per-tenant
            goodput accounting on it; a direct replica parses and
            ignores it)
            u8 0x5C | u64 decode opts (continuous-batching decode
            request, servers with a decode engine only: low 32 bits =
            max_new_tokens, bit 63 set = ONE-SHOT — collect the whole
            sequence into today's single reply. Without bit 63 the
            reply is a CHUNKED STREAM: zero or more frames with
            status 3 (one token-array chunk each, a frame per token
            batch), terminated by exactly one frame with status 0
            (the final chunk, possibly a zero-length array) or 1/2 on
            error/shed — the client concatenates the chunks. Input
            array 0 is the prompt (1-D int32/int64 token ids; the
            token chunks echo its dtype), further arrays are the
            model's per-sequence features. The 0xDD deadline field
            becomes a PER-TOKEN budget: time to first token and every
            inter-token gap.)
          Old servers ignore the trailing bytes; old clients simply
          omit them — both directions stay compatible: only a client
          that sent 0x5C without bit 63 ever sees status 3.
        3 health  payload = (empty); response body is UTF-8 JSON
            liveness/readiness: scheduler alive + heartbeat age,
            quarantined buckets, queue depth, draining flag, plus
            ``accepting`` (false once a drain began — route no new
            work here, but in-flight requests still finish) and
            ``draining_deadline_s`` (seconds the drain will still
            wait; null when not draining). Absent fields mean
            accepting: servers predating them never drain-announce.
        4 reload  payload = optional UTF-8 model prefix (empty = same
            prefix); the server loads + warms the new model OFF TO THE
            SIDE, swaps it in atomically, then drains the old engine —
            zero dropped requests, zero post-swap cold compiles for
            declared buckets. Response body is UTF-8 JSON.
        5 stats  payload = (empty); response body is a UTF-8 JSON
            object with the batching-engine counters (per-bucket
            compiles/hits/latency, breaker states, queue depth,
            shed_count) — or {"engine": null} when serving without an
            engine
        8 drain  payload = optional f64 drain budget in seconds; marks
            the server not-accepting (health: accepting=false,
            draining_deadline_s counts down) WITHOUT stopping it —
            in-flight and even newly-arriving requests still serve,
            but a fleet router that honors the flag stops routing here
            (how the fleet scales down / hot-reloads with zero drops:
            drain, wait for the router's in-flight count to reach
            zero, then reload or cmd-7 stop). Response is the health
            JSON. `undrain` = cmd 8 with f64 < 0: re-open admission.
        9 kv_put  payload = one kv-snapshot block (wire_spec
            "KV snapshots"); stateless preflight: the server validates
            the block against its own identity (model fingerprint,
            weights digest, quant mode, mesh) and limits without
            decoding anything. status 0 + the JSON header echoed =
            this replica could resume it; 2 = valid block, wrong
            replica (identity/capacity skew — try another); 1 =
            malformed block.
        10 kv_resume  payload = one kv-snapshot block, then the same
            optional trailing marker fields as cmd 1. The server
            restores the sequence at its exact position and replies
            EXACTLY like a streaming cmd-1 decode request (status-3
            chunks carrying only tokens AFTER the snapshot position,
            then one terminal frame); an identity skew is a status-2
            terminal, never silent wrong tokens. Servers without a
            decode engine answer status 1.
        6 metrics  payload = (empty); response body is the Prometheus
            text exposition (format 0.0.4) of the process obs registry:
            engine counters, server conn/frame counters, resilience
            counters, goodput, compile-ledger totals. The same text is
            served over HTTP by ``serve_model(metrics_port=...)``.
        7 stop
  response: u32 body_len | u8 status | (cmd 1: same per-output encoding)
  status: 0 ok | 1 error | 2 retryable (request shed by the batching
          engine's bounded queue, a quarantined bucket, a scheduler
          restart, or an expired deadline — back off and retry)
          | 3 stream chunk, more frames follow (streaming decode
          replies only — never sent unless the request carried the
          0x5C field without its one-shot bit)
"""
import json
import os
import socket
import struct
import threading
import time

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import prometheus as obs_prometheus
from ..obs import tracing as obs_tracing
from .batching import EngineClosed, RetryableError
# every wire constant comes from the ONE machine-readable spec
# (wire_spec.py) — the protocol lint (tools/tracelint.py --protocol)
# fails on any hardcoded wire literal reintroduced here, so this file
# can never drift from the spec (or from the Go/R/C clients, which the
# same lint diffs against it)
from . import wire_spec
from .wire_spec import (CMD_DRAIN, CMD_HEALTH, CMD_INFER, CMD_KV_PUT,
                        CMD_KV_RESUME, CMD_METRICS, CMD_RELOAD, CMD_STATS,
                        CMD_STOP, DEADLINE_MARKER, DECODE_MARKER,
                        DECODE_ONESHOT_BIT, TENANT_MARKER, TRACE_MARKER)

# historical aliases (the router and the serving tests import these
# names from here): the tables live in wire_spec now
_DTYPES = wire_spec.NUMPY_BY_CODE
_DTYPE_CODES = wire_spec.CODE_BY_NUMPY

STATUS_OK = wire_spec.STATUS_OK
STATUS_ERROR = wire_spec.STATUS_ERROR
STATUS_OVERLOADED = wire_spec.STATUS_RETRYABLE  # == RetryableError.status_code
STATUS_STREAM = wire_spec.STATUS_STREAM  # non-final streaming chunk

# Machine-checked lock order (tools/tracelint.py --concurrency, TPU309):
# one reload at a time (coarse, dedicated) > the backend swap lock (held
# only for the pointer swap) > the engine's own lock. The serving path
# (_handle/_infer) takes _backend_lock alone, so reload's long
# load+warmup never stalls a request.
# tpu-lock-order: PredictorServer._reload_lock < PredictorServer._backend_lock  # swap happens inside a reload
# tpu-lock-order: PredictorServer._reload_lock < BatchingEngine._lock  # reload warms/closes engines
# tpu-lock-order: PredictorServer._backend_lock < Metric._lock  # counters bump under the swap lock

# Hardening knobs: a 4-byte length prefix from a buggy/malicious client
# must not trigger an unbounded allocation, and a stalled client must
# not pin a handler thread forever.
MAX_BODY_BYTES = int(os.environ.get("PADDLE_TPU_SERVER_MAX_BODY",
                                    64 * 1024 * 1024))
RECV_TIMEOUT = float(os.environ.get("PADDLE_TPU_SERVER_RECV_TIMEOUT", 30.0))
DRAIN_TIMEOUT = float(os.environ.get("PADDLE_TPU_SERVER_DRAIN_TIMEOUT", 10.0))


class BodyTooLarge(ValueError):
    pass


def _read_all(sock, n, limit=None):
    if limit is not None and n > limit:
        raise BodyTooLarge(f"frame of {n} bytes exceeds cap {limit}")
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


# The codec lives in wire_spec (the one Python encoder/decoder of the
# framing); these historical underscore names are what the rest of the
# repo — router, fleet, the serving test tree — imports from here.
_encode_arrays = wire_spec.encode_arrays
_encode_deadline = wire_spec.encode_deadline
_encode_trace = wire_spec.encode_trace
_encode_tenant = wire_spec.encode_tenant
_encode_decode_opts = wire_spec.encode_decode_opts
_decode_arrays_off = wire_spec.decode_arrays_off
_decode_arrays = wire_spec.decode_arrays
_decode_request = wire_spec.decode_request


class PredictorServer:
    """Serve `predictor` (an inference.Predictor or any callable taking
    numpy arrays and returning a list of numpy arrays) on a TCP port.

    With ``engine`` (an inference.batching.BatchingEngine), cmd-1 infer
    requests from ALL connections route through the engine's scheduler:
    concurrent clients coalesce into padded shape-bucket batches, the
    bounded queue sheds overload as wire status 2 instead of queuing
    unboundedly, and the ``stats`` command (cmd 5) exposes the
    per-bucket compile/hit/latency counters.

    With ``loader`` (a callable ``prefix -> (run_fn, engine_or_None)``,
    supplied by :func:`serve_model`), the ``reload`` wire command (cmd
    4) hot-swaps the served model: the new model loads and warms up off
    to the side, the (run_fn, engine) pair swaps atomically, and the old
    engine drains — in-flight requests complete on the old programs, a
    handler that raced the swap retries once on the new engine, and
    declared buckets are precompiled so no post-swap request pays a
    cold compile."""

    # tpu-resource: acquires=router_socket
    def __init__(self, run_fn, port=0, host="127.0.0.1",
                 max_body=MAX_BODY_BYTES, recv_timeout=RECV_TIMEOUT,
                 engine=None, own_engine=False, loader=None, prefix=None,
                 decode_engine=None, own_decode_engine=False, phase=None):
        self._run = run_fn
        self._engine = engine
        # phase: this replica's pool in a disaggregated fleet
        # (wire_spec.REPLICA_PHASES; env default
        # PADDLE_TPU_SERVING_PHASE). Declared in the cmd-3 health body
        # (and echoed by cmd 5) so the registry can pool replicas; an
        # attached decode engine's own phase wins when none is given —
        # the engine's warmup ladder is the thing the phase shapes.
        if phase is None:
            phase = (getattr(decode_engine, "phase", None)
                     or os.environ.get("PADDLE_TPU_SERVING_PHASE")
                     or "both")
        if phase not in wire_spec.REPLICA_PHASES:
            raise ValueError(
                f"unknown replica phase {phase!r} (expected one of "
                f"{wire_spec.REPLICA_PHASES})")
        self.phase = phase
        # own_engine: this server is the engine's only handle (serve_model
        # builds one per server) and must close it on stop, or its
        # scheduler thread + compiled programs leak per server lifecycle
        self._own_engine = own_engine and engine is not None
        # continuous-batching decode engine (inference.decode): cmd-1
        # requests carrying the 0x5C field route here and reply as a
        # chunked stream (or a one-shot collected reply)
        self._decode_engine = decode_engine
        self._own_decode_engine = (own_decode_engine
                                   and decode_engine is not None)
        self._decode_stream_timeout = float(os.environ.get(
            "PADDLE_TPU_SERVER_DECODE_TIMEOUT", 300.0))
        self._loader = loader
        self._prefix = prefix
        self._backend_lock = threading.Lock()  # guards _run/_engine swap
        self._reload_lock = threading.Lock()  # one reload at a time
        self._reload_count = 0
        self._max_body = max_body
        self._recv_timeout = recv_timeout
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._conns = {}  # thread -> {"conn": socket, "busy": bool}
        self._conns_lock = threading.Lock()
        # drain announcement (cmd 8 / begin_drain / stop): while
        # _accepting is False the server still serves everything it
        # receives, but health JSON tells routers to stop sending new
        # work. Guarded by _conns_lock (written from handler threads
        # via cmd 8 and from whoever calls stop()).
        self._accepting = True
        self._draining_deadline = None  # monotonic, or None
        # optional /metrics HTTP endpoint (obs.httpd.MetricsServer),
        # attached by serve_model(metrics_port=...); stop() closes it
        self.metrics_server = None
        self._init_metrics()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _init_metrics(self):
        """Per-server obs instruments, exposed through the process
        registry by a collector (unregistered in stop())."""
        import weakref

        cl = {"port": str(self.port)}
        self._m_conns = obs_metrics.Counter(
            "paddle_server_connections_total",
            "Accepted client connections", const_labels=cl)
        self._m_frames = obs_metrics.Counter(
            "paddle_server_frames_total",
            "Request frames received, by wire command",
            labelnames=("cmd",), const_labels=cl)
        self._m_responses = obs_metrics.Counter(
            "paddle_server_responses_total",
            "cmd-1 infer responses, by wire status "
            "(0 ok, 1 error, 2 retryable)",
            labelnames=("status",), const_labels=cl)
        self._m_reloads = obs_metrics.Counter(
            "paddle_server_reloads_total",
            "Hot model reloads", const_labels=cl)
        self._m_open = obs_metrics.Gauge(
            "paddle_server_connections_open",
            "Currently-connected clients", const_labels=cl)
        self._m_chunks = obs_metrics.Counter(
            "paddle_server_stream_chunks_total",
            "Streaming decode reply frames sent (status 3 + terminal)",
            const_labels=cl)
        self._server_instruments = [
            self._m_conns, self._m_frames, self._m_responses,
            self._m_reloads, self._m_open, self._m_chunks]
        ref = weakref.ref(self)

        def _collector():
            srv = ref()
            if srv is None:
                return None  # GC'd server: registry auto-unregisters
            with srv._conns_lock:
                srv._m_open.set(len(srv._conns))
            return [m.collect() for m in srv._server_instruments]

        self._obs_collector = _collector
        obs_metrics.REGISTRY.register_collector(_collector)

    def _serve(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            self._m_conns.inc()
            t = threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True)
            with self._conns_lock:
                self._conns[t] = {"conn": conn, "busy": False}
            t.start()

    def _set_busy(self, busy):
        with self._conns_lock:
            ent = self._conns.get(threading.current_thread())
            if ent is not None:
                ent["busy"] = busy

    def _backend(self):
        with self._backend_lock:
            return self._run, self._engine

    def _stats_json(self):
        """Body of the `stats` wire command (cmd 5). Shape: the
        batching-engine counters at top level (as always), plus a
        ``decode`` key when a decode engine is attached."""
        _, engine = self._backend()
        stats = {"engine": None} if engine is None else engine.stats()
        stats = dict(stats)
        stats["phase"] = self.phase
        if self._decode_engine is not None:
            stats["decode"] = self._decode_engine.stats()
        return json.dumps(stats)

    def _health_json(self):
        """Body of the `health` wire command (cmd 3): liveness (is the
        serving path able to make progress) and readiness (is it
        accepting work) in one probe."""
        _, engine = self._backend()
        eng = engine.health() if engine is not None else None
        dec = (self._decode_engine.health()
               if self._decode_engine is not None else None)
        with self._conns_lock:
            conns = len(self._conns)
            accepting = self._accepting and not self._stop.is_set()
            dl = self._draining_deadline
        draining = not accepting
        ok = (not draining and (eng is None or eng["ok"])
              and (dec is None or dec["ok"]))
        return json.dumps({
            "ok": ok,
            "phase": self.phase,
            "decode": dec,
            "draining": draining,
            # readiness split (backward-compatible: absent fields mean
            # accepting): a router distinguishes "draining, stop
            # routing but in-flight work finishes" from "dead"
            "accepting": accepting,
            "draining_deadline_s": (None if (accepting or dl is None)
                                    else round(max(0.0,
                                                   dl - time.monotonic()),
                                               3)),
            "connections": conns,
            "reloads": self._reload_count,
            "engine": eng,
        })

    def begin_drain(self, deadline_s=None):
        """Announce a drain (the `drain` wire command, cmd 8): health
        flips to accepting=false so routers stop sending new work, but
        the server keeps serving whatever arrives — the zero-drop half
        of a scale-down or router-orchestrated reload. ``deadline_s``
        is advisory (exported as ``draining_deadline_s``); a negative
        value cancels the drain and re-opens admission."""
        with self._conns_lock:
            if deadline_s is not None and deadline_s < 0:
                self._accepting = True
                self._draining_deadline = None
            else:
                self._accepting = False
                self._draining_deadline = (
                    None if deadline_s is None
                    else time.monotonic() + float(deadline_s))

    # ------------------------------------------------------------- reload
    def reload(self, prefix=None):
        """Atomic hot weight swap (the `reload` wire command, cmd 4).

        Load + warm the new model off to the side (requests keep being
        served by the old one the whole time), swap the (run_fn, engine)
        pair under the backend lock, then close the old engine — which
        drains its in-flight batches. Declared buckets of the old engine
        are precompiled on the new one BEFORE the swap, so post-swap
        traffic never pays a cold compile for them."""
        if self._loader is None:
            raise RuntimeError(
                "this server has no model loader; hot reload needs a "
                "server constructed by serve_model(...) (a bare "
                "PredictorServer wraps an opaque callable)")
        with self._reload_lock:
            if self._stop.is_set():
                # stop() closes the serving engine; a reload racing past
                # it would swap in a fresh engine (scheduler + watchdog
                # + compiled programs) that nothing ever closes
                raise RuntimeError("server is stopping; reload refused")
            new_prefix = prefix or self._prefix
            old_engine = self._backend()[1]
            new_run, new_engine = self._loader(new_prefix)
            warmed = []
            try:
                if new_engine is not None:
                    declared = (old_engine.declared_buckets()
                                if old_engine is not None else None)
                    # warm the same buckets the old engine declared (or
                    # the full power-of-2 ladder) before any request can
                    # see the new engine. The reload lock is dedicated
                    # (one reload at a time) and requests keep flowing
                    # under _backend_lock the whole time — holding it
                    # across the multi-second warmup stalls nobody.
                    warmed = new_engine.warmup(declared or None)  # tpu-lint: disable=TPU302  # dedicated coarse lock; serving path never takes it
                with self._backend_lock:
                    if self._stop.is_set():
                        # stop() closed the serving engine while we were
                        # loading; swapping now would hand the server an
                        # engine nothing ever closes
                        raise RuntimeError(
                            "server stopped during reload; new model "
                            "discarded")
                    old_run, old_engine = self._run, self._engine
                    old_owned = self._own_engine
                    self._run, self._engine = new_run, new_engine
                    self._own_engine = new_engine is not None
                    self._prefix = new_prefix
                    self._reload_count += 1
                    self._m_reloads.inc()
            except BaseException:
                # a failed load/warmup (or a stop racing us) must not
                # leak the freshly built engine's scheduler + watchdog
                # threads and compiled programs
                if new_engine is not None:
                    new_engine.close()
                raise
            if old_engine is not None and old_owned:
                # drains: pending groups on the old engine still fire
                old_engine.close()
            return {"reloaded": True, "prefix": new_prefix,
                    "warm_buckets": list(warmed),
                    "reloads": self._reload_count}

    # ------------------------------------------------------------ handler
    def _infer(self, inputs, budget, trace_id):
        """Run one NON-STREAMING cmd-1 infer request (already parsed);
        returns the encoded response frame body (status + payload)."""
        deadline = (None if budget is None
                    else time.monotonic() + budget)
        t0 = time.perf_counter()
        if budget is not None and budget <= 0.0:
            # the client's budget was spent before the frame finished
            # arriving: drop before dispatch, spend no compute
            return struct.pack("<B", STATUS_OVERLOADED)
        for attempt in (0, 1):
            run, engine = self._backend()
            try:
                if engine is not None:
                    outputs = engine.infer(inputs, deadline=deadline,
                                           trace_id=trace_id)
                else:
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        return struct.pack("<B", STATUS_OVERLOADED)
                    outputs = run(*inputs)
                break
            except EngineClosed:
                # the engine was hot-swapped between our snapshot and
                # the submit: retry once on the new backend so a reload
                # never drops a request
                if attempt:
                    raise
        if not isinstance(outputs, (list, tuple)):
            outputs = [outputs]
        outputs = [np.asarray(o._value if hasattr(o, "_value")
                              else o) for o in outputs]
        enc = _encode_arrays(outputs)
        if trace_id is not None:
            # the handler-side span: decode -> dispatch -> encode (the
            # engine's serving.request span nests inside this window)
            obs_tracing.record_span(
                "serving.reply", time.perf_counter() - t0,
                trace_id=trace_id, port=self.port)
        return struct.pack("<B", STATUS_OK) + enc

    # ------------------------------------------------- streaming decode
    def _send_frame(self, conn, status, payload=b""):
        conn.sendall(struct.pack("<IB", 1 + len(payload), status)
                     + payload)
        self._m_chunks.inc()

    def _serve_decode(self, conn, inputs, budget, trace_id, opts):
        """One cmd-1 decode request (0x5C field present): submit to
        the decode engine and reply as a chunk stream (or a single
        collected reply in one-shot mode). Sends its own frames;
        counts the TERMINAL status in the response counter.

        If the client vanishes mid-stream (sendall fails) the request
        is cancelled so its KV slot frees immediately — a dead reader
        must never ride the batch to max_new_tokens against the slot
        cap (the ISSUE 12 slot-leak audit)."""
        dec = self._decode_engine
        if dec is None or not inputs:
            self._m_responses.inc(status=str(STATUS_ERROR))
            enc = b"no decode engine attached to this server"
            conn.sendall(struct.pack("<IB", 1 + len(enc), STATUS_ERROR)
                         + enc)
            return
        t0 = time.perf_counter()
        if opts.get("handoff"):
            self._serve_prefill_handoff(conn, dec, inputs, budget,
                                        trace_id, t0)
            return
        try:
            req = dec.submit(inputs[0], features=list(inputs[1:]),
                             max_new_tokens=opts.get("max_new_tokens"),
                             token_budget_s=budget, trace_id=trace_id,
                             snapshot_every=opts.get("snapshot_every")
                             or None,
                             speculative=bool(opts.get("speculative")))
        except (RetryableError, EngineClosed):
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            conn.sendall(struct.pack("<IB", 1, STATUS_OVERLOADED))
            return
        except Exception:  # noqa: BLE001 - bad request (shape/dtype)
            self._m_responses.inc(status=str(STATUS_ERROR))
            conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
            return
        if opts.get("oneshot"):
            try:
                tokens = req.result(timeout=self._decode_stream_timeout)
            except (RetryableError, EngineClosed, TimeoutError):
                dec.cancel(req)
                self._m_responses.inc(status=str(STATUS_OVERLOADED))
                conn.sendall(struct.pack("<IB", 1, STATUS_OVERLOADED))
                return
            except Exception:  # noqa: BLE001 - protocol error status
                dec.cancel(req)
                self._m_responses.inc(status=str(STATUS_ERROR))
                conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                return
            enc = _encode_arrays([tokens])
            self._m_responses.inc(status=str(STATUS_OK))
            conn.sendall(struct.pack("<I", 1 + len(enc))
                         + struct.pack("<B", STATUS_OK) + enc)
            if trace_id is not None:
                obs_tracing.record_span(
                    "serving.reply", time.perf_counter() - t0,
                    trace_id=trace_id, port=self.port,
                    tokens=int(tokens.size))
            return
        # chunk stream: one frame per available token batch
        self._stream_tokens(
            conn, dec, req, t0, trace_id,
            emit_snapshots=bool(opts.get("snapshot_every")))

    def _serve_prefill_handoff(self, conn, dec, inputs, budget,
                               trace_id, t0):
        """cmd-1 with the 0x5C prefill-handoff bit: the disaggregated
        fleet's prefill leg. Runs ONLY the prefill step — the request
        is forced to max_new_tokens=1 with snapshot cadence 1 so the
        engine assembles the n_generated=1 block at the prefill
        boundary — and replies deterministically with exactly two
        frames: one status-3 kv-snapshot frame, then the terminal
        status-0 frame carrying the first token. The router holds the
        block, forwards the token, and seeds a decode replica over
        kv_put/kv_resume. A replica that cannot produce the block
        answers status 2 (retryable) so the leg re-runs elsewhere —
        never a torn stream, never silent token loss."""
        try:
            req = dec.submit(inputs[0], features=list(inputs[1:]),
                             max_new_tokens=1, token_budget_s=budget,
                             trace_id=trace_id, snapshot_every=1)
        except (RetryableError, EngineClosed):
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            self._send_frame(conn, STATUS_OVERLOADED)
            return
        except Exception:  # noqa: BLE001 - bad request (shape/dtype)
            self._m_responses.inc(status=str(STATUS_ERROR))
            self._send_frame(conn, STATUS_ERROR)
            return
        try:
            tokens = req.result(timeout=self._decode_stream_timeout)
        except (RetryableError, EngineClosed, TimeoutError):
            dec.cancel(req)
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            self._send_frame(conn, STATUS_OVERLOADED)
            return
        except Exception:  # noqa: BLE001 - protocol error status
            dec.cancel(req)
            self._m_responses.inc(status=str(STATUS_ERROR))
            self._send_frame(conn, STATUS_ERROR)
            return
        blob = req.latest_snapshot()
        if blob is None:
            # the boundary snapshot was dropped (snapshot assembly is
            # degraded-never-fatal): without the block there is nothing
            # to hand off — answer retryable so the router re-runs the
            # prefill elsewhere or degrades to colocated serving
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            self._send_frame(conn, STATUS_OVERLOADED)
            return
        self._send_frame(conn, STATUS_STREAM, blob)
        self._m_responses.inc(status=str(STATUS_OK))
        self._send_frame(conn, STATUS_OK, _encode_arrays([tokens]))
        if trace_id is not None:
            obs_tracing.record_span(
                "serving.reply", time.perf_counter() - t0,
                trace_id=trace_id, port=self.port,
                tokens=int(tokens.size))

    def _stream_tokens(self, conn, dec, req, t0, trace_id,
                       emit_snapshots=False, sent=0):
        """Drain one decode request onto the wire as a chunk stream
        (status-3 token frames, one terminal frame) — shared by a
        streaming cmd-1 decode reply and a cmd kv_resume reply.

        With ``emit_snapshots`` (the request carried a snapshot
        cadence), each freshly-taken kv-snapshot block goes out as an
        EXTRA status-3 frame — but only once every token it covers is
        already on the wire (``sent`` >= its n_generated), so a
        consumer holding the newest snapshot has always fully
        delivered its position (the router's dedup arithmetic depends
        on exactly this ordering). ``sent`` starts at the snapshot
        position for a resumed stream: snapshot n_generated counts
        from the start of the sequence.

        If the client vanishes mid-stream (sendall fails) the request
        is cancelled so its KV slot frees immediately — a dead reader
        must never ride the batch to max_new_tokens against the slot
        cap (the ISSUE 12 slot-leak audit)."""
        pending = None
        try:
            while True:
                try:
                    toks, done = req.next_tokens(
                        timeout=self._decode_stream_timeout)
                except (RetryableError, EngineClosed, TimeoutError):
                    dec.cancel(req)
                    self._m_responses.inc(status=str(STATUS_OVERLOADED))
                    self._send_frame(conn, STATUS_OVERLOADED)
                    return
                except Exception:  # noqa: BLE001 - protocol error status
                    dec.cancel(req)
                    self._m_responses.inc(status=str(STATUS_ERROR))
                    self._send_frame(conn, STATUS_ERROR)
                    return
                arr = np.asarray(toks, dtype=req.token_dtype)
                sent += arr.size
                if done:
                    self._m_responses.inc(status=str(STATUS_OK))
                    self._send_frame(conn, STATUS_OK,
                                     _encode_arrays([arr]))
                    if trace_id is not None:
                        obs_tracing.record_span(
                            "serving.reply", time.perf_counter() - t0,
                            trace_id=trace_id, port=self.port,
                            tokens=sent)
                    return
                self._send_frame(conn, STATUS_STREAM,
                                 _encode_arrays([arr]))
                if emit_snapshots:
                    got = req.take_snapshot()
                    if got is not None:
                        pending = got
                    if pending is not None and pending[1] <= sent:
                        self._send_frame(conn, STATUS_STREAM, pending[0])
                        pending = None
        except (OSError, ConnectionError):
            # the reader is gone mid-stream: free the KV slot NOW
            dec.cancel(req)
            raise

    def _serve_kv_put(self, conn, payload):
        """cmd kv_put: snapshot preflight against THIS replica
        (``DecodeEngine.seed_check`` — the identity validation shared
        with the resume path, so acceptance here can never drift from
        what a resume actually demands, PLUS a fresh-slot capacity
        check: a prefill->decode handoff seeds a NEW sequence here, so
        a replica that cannot admit one now refuses retryable instead
        of absorbing it). status 0 echoes the JSON header; a refusal
        is status 2; a malformed block is status 1."""
        dec = self._decode_engine
        if dec is None:
            self._m_responses.inc(status=str(STATUS_ERROR))
            enc = b"no decode engine attached to this server"
            conn.sendall(struct.pack("<IB", 1 + len(enc), STATUS_ERROR)
                         + enc)
            return
        try:
            header, _ = dec.seed_check(payload)
        except (RetryableError, EngineClosed) as e:
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            enc = str(e).encode("utf-8", errors="replace")
            conn.sendall(struct.pack("<IB", 1 + len(enc),
                                     STATUS_OVERLOADED) + enc)
            return
        except Exception as e:  # noqa: BLE001 - malformed block
            self._m_responses.inc(status=str(STATUS_ERROR))
            enc = str(e).encode("utf-8", errors="replace")
            conn.sendall(struct.pack("<IB", 1 + len(enc), STATUS_ERROR)
                         + enc)
            return
        enc = json.dumps(header, sort_keys=True).encode("utf-8")
        self._m_responses.inc(status=str(STATUS_OK))
        conn.sendall(struct.pack("<IB", 1 + len(enc), STATUS_OK) + enc)

    def _serve_kv_resume(self, conn, payload):
        """cmd kv_resume: restore a snapshotted sequence on this
        replica's decode engine and stream its continuation. The reply
        shape is EXACTLY a streaming cmd-1 decode reply (status-3
        chunks carrying only tokens AFTER the snapshot position, one
        terminal frame), so the router's relay loop handles both
        identically; an identity skew is a status-2 terminal."""
        dec = self._decode_engine
        if dec is None:
            self._m_responses.inc(status=str(STATUS_ERROR))
            enc = b"no decode engine attached to this server"
            conn.sendall(struct.pack("<IB", 1 + len(enc), STATUS_ERROR)
                         + enc)
            return
        t0 = time.perf_counter()
        try:
            (header, _arrays, budget, trace_id, opts,
             snap_end) = wire_spec.decode_kv_resume(payload)
        except Exception:  # noqa: BLE001 - malformed body
            self._m_responses.inc(status=str(STATUS_ERROR))
            conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
            return
        opts = opts or {}
        try:
            req = dec.resume(payload[:snap_end], token_budget_s=budget,
                             speculative=bool(opts.get("speculative")),
                             trace_id=trace_id,
                             snapshot_every=opts.get("snapshot_every"),
                             max_new_tokens=opts.get("max_new_tokens"))
        except (RetryableError, EngineClosed):
            # identity/capacity skew or shed: the snapshot may resume
            # elsewhere — a refusal is ALWAYS a status-2 terminal,
            # never silent wrong tokens
            self._m_responses.inc(status=str(STATUS_OVERLOADED))
            self._send_frame(conn, STATUS_OVERLOADED)
            return
        except Exception:  # noqa: BLE001 - inconsistent block
            self._m_responses.inc(status=str(STATUS_ERROR))
            self._send_frame(conn, STATUS_ERROR)
            return
        if opts.get("oneshot"):
            # collect-the-rest mode: one reply with the FULL sequence
            try:
                tokens = req.result(timeout=self._decode_stream_timeout)
            except (RetryableError, EngineClosed, TimeoutError):
                dec.cancel(req)
                self._m_responses.inc(status=str(STATUS_OVERLOADED))
                self._send_frame(conn, STATUS_OVERLOADED)
                return
            except Exception:  # noqa: BLE001 - protocol error status
                dec.cancel(req)
                self._m_responses.inc(status=str(STATUS_ERROR))
                self._send_frame(conn, STATUS_ERROR)
                return
            enc = _encode_arrays([tokens])
            self._m_responses.inc(status=str(STATUS_OK))
            conn.sendall(struct.pack("<I", 1 + len(enc))
                         + struct.pack("<B", STATUS_OK) + enc)
            return
        self._stream_tokens(
            conn, dec, req, t0, trace_id,
            emit_snapshots=bool(opts.get("snapshot_every")),
            sent=int(header["n_generated"]))

    def _handle(self, conn):
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            while not self._stop.is_set():
                # idle between frames: block without timeout — keep-alive
                # connections may sit quiet for minutes (stop() unblocks
                # this recv by closing the socket). Once the first header
                # byte arrives, a frame is in flight: a peer that stalls
                # mid-frame times out instead of pinning this thread.
                conn.settimeout(None)
                first = conn.recv(1)
                if not first:
                    raise ConnectionError("peer closed")
                conn.settimeout(self._recv_timeout)
                (blen,) = struct.unpack("<I", first + _read_all(conn, 3))
                if blen == 0:
                    # malformed (a body always has at least the cmd
                    # byte) but the stream is still in sync: report and
                    # keep serving
                    conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                    continue
                self._set_busy(True)  # a frame is in flight: drain waits
                try:
                    body = _read_all(conn, blen, limit=self._max_body)
                except BodyTooLarge:
                    # cap exceeded: error status, then close — the rest
                    # of the oversized frame is unread, so the stream
                    # cannot be resynced
                    conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                    return
                cmd = body[0]
                self._m_frames.inc(cmd=str(cmd))
                if cmd == CMD_STOP:
                    conn.sendall(struct.pack("<IB", 1, STATUS_OK))
                    threading.Thread(target=self.stop, daemon=True).start()
                    return
                if cmd == CMD_HEALTH:
                    enc = self._health_json().encode("utf-8")
                    conn.sendall(struct.pack("<IB", 1 + len(enc),
                                             STATUS_OK) + enc)
                    self._set_busy(False)
                    continue
                if cmd == CMD_METRICS:
                    enc = obs_prometheus.render().encode("utf-8")
                    conn.sendall(struct.pack("<IB", 1 + len(enc),
                                             STATUS_OK) + enc)
                    self._set_busy(False)
                    continue
                if cmd == CMD_RELOAD:
                    prefix = body[1:].decode("utf-8", errors="replace")
                    try:
                        info = self.reload(prefix or None)
                        enc = json.dumps(info).encode("utf-8")
                        conn.sendall(struct.pack("<IB", 1 + len(enc),
                                                 STATUS_OK) + enc)
                    except Exception as e:  # noqa: BLE001 - wire error
                        enc = str(e).encode("utf-8", errors="replace")
                        conn.sendall(struct.pack("<IB", 1 + len(enc),
                                                 STATUS_ERROR) + enc)
                    self._set_busy(False)
                    continue
                if cmd == CMD_STATS:
                    enc = self._stats_json().encode("utf-8")
                    conn.sendall(struct.pack("<IB", 1 + len(enc),
                                             STATUS_OK) + enc)
                    self._set_busy(False)
                    continue
                if cmd == CMD_DRAIN:
                    deadline_s = (struct.unpack("<d", body[1:9])[0]
                                  if len(body) >= 9 else None)
                    self.begin_drain(deadline_s)
                    enc = self._health_json().encode("utf-8")
                    conn.sendall(struct.pack("<IB", 1 + len(enc),
                                             STATUS_OK) + enc)
                    self._set_busy(False)
                    continue
                if cmd == CMD_KV_PUT:
                    self._serve_kv_put(conn, body[1:])
                    self._set_busy(False)
                    continue
                if cmd == CMD_KV_RESUME:
                    self._serve_kv_resume(conn, body[1:])
                    self._set_busy(False)
                    continue
                if cmd != CMD_INFER:
                    conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                    self._set_busy(False)
                    continue
                try:
                    parsed = _decode_request(body[1:])
                except Exception:  # noqa: BLE001 - malformed body
                    self._m_responses.inc(status=str(STATUS_ERROR))
                    conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                    self._set_busy(False)
                    continue
                if parsed[3] is not None:
                    # decode request (0x5C field): chunked streaming
                    # reply (or one-shot collect) — sends its own frames
                    self._serve_decode(conn, parsed[0], parsed[1],
                                       parsed[2], parsed[3])
                    self._set_busy(False)
                    continue
                try:
                    resp = self._infer(parsed[0], parsed[1], parsed[2])
                    self._m_responses.inc(status=str(resp[0]))
                    conn.sendall(struct.pack("<I", len(resp)) + resp)
                except (RetryableError, EngineClosed):
                    # load shed / quarantined bucket / scheduler restart
                    # / expired deadline: a fast, explicit rejection the
                    # client can retry — never an unbounded queue, never
                    # a hang. EngineClosed (a request racing back-to-back
                    # reloads or a stop past _infer's one retry) is
                    # equally transient: the next attempt lands on the
                    # swapped-in engine or a cleanly-restarted server.
                    self._m_responses.inc(status=str(STATUS_OVERLOADED))
                    conn.sendall(struct.pack("<IB", 1, STATUS_OVERLOADED))
                except Exception:  # noqa: BLE001 - protocol error status
                    self._m_responses.inc(status=str(STATUS_ERROR))
                    conn.sendall(struct.pack("<IB", 1, STATUS_ERROR))
                self._set_busy(False)
        except socket.timeout:
            pass
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()
            with self._conns_lock:
                self._conns.pop(threading.current_thread(), None)

    # tpu-resource: releases=router_socket
    def stop(self, drain=True, timeout=DRAIN_TIMEOUT):
        """Graceful shutdown: stop accepting, let requests that are
        mid-processing finish (up to `timeout`), force-close idle
        keep-alive connections — a rolling restart neither drops a
        response mid-write nor hangs on a silent client."""
        # the drain announcement first: a health probe that races the
        # shutdown (over an already-open connection) reads
        # accepting=false + the drain budget, not a confusing
        # "ok but about to vanish"
        self.begin_drain(timeout if drain else 0.0)
        self._stop.set()
        obs_metrics.REGISTRY.unregister_collector(self._obs_collector)
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        # a reload mid-flight cannot swap past us: its swap re-checks
        # _stop under _backend_lock (set above, before our engine read
        # below) and aborts, closing its own new engine — so the engine
        # we read here is the one that is actually serving, and stop()
        # never waits out a multi-second model load
        try:
            # shutdown BEFORE close: on Linux, close() alone does not
            # wake a thread already blocked in accept() — the accept
            # loop would park forever and anything join()ing it (a
            # serve-until-stopped wrapper process) would hang with it
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()  # no new connections
        except OSError:
            pass
        with self._backend_lock:
            engine = self._engine if self._own_engine else None
        dec = self._decode_engine if self._own_decode_engine else None
        if not drain:
            if engine is not None:
                engine.close()
            if dec is not None:
                dec.close()
            return
        me = threading.current_thread()
        deadline = time.monotonic() + timeout
        with self._conns_lock:
            entries = [(t, e) for t, e in self._conns.items() if t is not me]
        for t, ent in entries:
            if ent["busy"]:
                t.join(max(0.0, deadline - time.monotonic()))
        # whoever is left is idle (blocked waiting for the next frame) or
        # overran the drain window — unblock by closing the socket
        with self._conns_lock:
            leftover = [e["conn"] for t, e in self._conns.items()
                        if t is not me]
        for c in leftover:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if engine is not None:
            # handlers are drained/unblocked; pending engine requests
            # still fire (close() lets partial batches complete)
            engine.close()
        if dec is not None:
            # streaming handlers were unblocked above; in-flight
            # sequences fail retryable (a stop mid-stream is a shed,
            # never silent truncation)
            dec.close()


def serve_model(path_prefix, port=0, dynamic_batching=False,
                max_batch_size=32, max_wait_ms=2.0, max_queue=256,
                warmup=True, metrics_port=None, quant=None, mesh=None,
                phase=None, **engine_kwargs):
    """Load a jit-saved model and serve it (the C API's server side).

    With ``dynamic_batching=True`` (needs a batch-polymorphic save, see
    jit.save) all connections share one BatchingEngine: requests
    coalesce into padded shape-bucket batches, declared buckets are
    precompiled up front, and saturation sheds as wire status 2. Extra
    ``engine_kwargs`` (breaker_threshold, watchdog_interval,
    artifact_store, ...) pass through to the BatchingEngine.

    With ``PADDLE_TPU_ARTIFACT_DIR`` set (or an explicit
    ``artifact_store=``), warmup — including the off-to-the-side warmup
    a hot reload performs — loads each bucket's program from the
    persistent compiled-artifact store instead of compiling: a fresh
    replica process reaches its first healthy reply with zero XLA
    compiles once any replica has published the ladder
    (tests/test_artifact_serving.py holds exactly this), and a corrupt or
    stale store entry silently degrades that bucket to an inline
    compile (README "Artifact store" has the degradation matrix).

    ``metrics_port`` (0 = any free port) additionally serves the
    Prometheus text exposition of the process obs registry on
    ``http://host:metrics_port/metrics`` — the scrape-friendly twin of
    the ``metrics`` wire command (cmd 6). The endpoint lives and dies
    with the server (``server.metrics_server.port`` has the bound
    port).

    ``quant`` (env default ``PADDLE_TPU_SERVING_QUANT``) declares the
    serving quantization mode this replica MUST serve (``"f32"`` |
    ``"w8"`` | ``"w8a8"`` | ``"bf16w"``): the loaded model's recorded
    mode (jit.save's ``quant=`` sidecar field) is checked at load time
    — and on every hot reload — so a fleet flipped to w8 can never
    silently serve an f32 save (or vice versa). Unset = serve whatever
    the save recorded.

    ``mesh`` (env default ``PADDLE_TPU_SERVING_MESH``) declares the
    serving mesh this replica shards its weights over (``"single"`` |
    ``"tp<k>"`` | ``"fsdp<m>"`` | ``"fsdp<m>xtp<k>"``; README "Sharded
    serving"). Sharded serving runs through the batching engine
    (``dynamic_batching=True``): weights are committed to the mesh once
    at load and every bucket program is a per-(bucket, mesh) pjit
    program with its own artifact-store identity — wire-transparent to
    all four clients. A save that recorded an intended mesh
    (``jit.save(..., mesh=...)``) is checked against the declared one
    at load time AND on every hot reload; the mesh resolved at first
    load is pinned, so a reload can never silently flip a replica's
    topology. Unset = serve whatever the save recorded (or
    single-chip).

    ``phase`` (env default ``PADDLE_TPU_SERVING_PHASE``) declares the
    replica's pool in a disaggregated prefill/decode fleet
    (``"prefill"`` | ``"decode"`` | ``"both"``; README "Disaggregated
    serving"): reported in the cmd-3 health body so a phase-pooled
    ``Fleet`` routes prompt ingestion and token generation to the
    right pool. Placement only — the replica still serves every
    command, so a fleet whose other pool collapsed degrades to
    colocated serving here.

    The returned server supports the ``reload`` wire command (cmd 4):
    re-save the model to the same (or a new) prefix and issue a reload
    to hot-swap weights with zero dropped requests."""
    from ..jit import load as jit_load
    from .sharding import SINGLE, ServingMesh

    if quant is None:
        quant = os.environ.get("PADDLE_TPU_SERVING_QUANT") or None
    if quant not in (None, "f32"):
        # fail at entry with the valid mode set — a typo'd deployment
        # knob ('W8', 'int8') must not surface later as a misleading
        # "re-save your model" mismatch error
        from ..quantization.serving import check_mode

        check_mode(quant)
    if mesh is None:
        mesh = os.environ.get("PADDLE_TPU_SERVING_MESH") or None
    # fail at entry with the valid descriptor grammar — same rationale
    # as the quant knob (a typo'd mesh must not surface as a
    # misleading save-mismatch error later)
    declared_mesh = (None if mesh is None
                     else ServingMesh.parse(mesh).descriptor)
    # the mesh resolved at FIRST load is pinned for the server's
    # lifetime: hot reload checks the new save against it, so a reload
    # can change weights, never the replica's topology
    pinned_mesh = {}

    def loader(prefix):
        layer = jit_load(prefix)
        if quant is not None:
            have = getattr(layer, "_quant_mode", None) or "f32"
            if have != quant:
                raise ValueError(
                    f"{prefix}: saved quant mode {have!r} does not "
                    f"match the declared serving mode {quant!r} "
                    "(PADDLE_TPU_SERVING_QUANT / serve_model(quant=)); "
                    "re-save with jit.save(..., quant=...) or fix the "
                    "deployment knob")
        recorded_mesh = getattr(layer, "_serving_mesh", None)
        want = (declared_mesh if declared_mesh is not None
                else pinned_mesh.get("desc"))
        if (want is not None and recorded_mesh is not None
                and recorded_mesh != want):
            raise ValueError(
                f"{prefix}: saved serving mesh {recorded_mesh!r} does "
                f"not match the declared mesh {want!r} "
                "(PADDLE_TPU_SERVING_MESH / serve_model(mesh=)); "
                "re-save with jit.save(..., mesh=...) or fix the "
                "deployment knob")
        eff_mesh = want or recorded_mesh or SINGLE
        pinned_mesh.setdefault("desc", eff_mesh)
        if eff_mesh != SINGLE and not dynamic_batching:
            raise ValueError(
                f"serving mesh {eff_mesh!r} needs the batching engine "
                "(the per-bucket pjit programs live there): pass "
                "dynamic_batching=True to serve_model")

        def run(*arrays):
            out = layer(*arrays)
            return out if isinstance(out, (list, tuple)) else [out]

        engine = None
        if dynamic_batching:
            from .batching import BatchingEngine

            engine = BatchingEngine.for_layer(
                layer, max_batch_size=max_batch_size,
                max_wait_ms=max_wait_ms, max_queue=max_queue,
                mesh=eff_mesh, **engine_kwargs)
        return run, engine

    run, engine = loader(path_prefix)
    if engine is not None and warmup:
        engine.warmup()
    server = PredictorServer(run, port=port, engine=engine,
                             own_engine=engine is not None,
                             loader=loader, prefix=path_prefix,
                             phase=phase)
    if metrics_port is not None:
        from ..obs.httpd import MetricsServer

        try:
            server.metrics_server = MetricsServer(metrics_port)
        except BaseException:
            server.stop(drain=False)
            raise
    return server
