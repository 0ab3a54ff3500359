"""Resumable decode streams (ISSUE 17): KV snapshot handoff over the
wire + mid-stream replica failover.

Layers covered here:

- engine: ``DecodeEngine`` snapshot/resume bitwise roundtrip (the
  resumed suffix equals the unbroken solo decode), boundary snapshots,
  and the identity-skew refusals (fingerprint / weights / quant /
  mesh) — a skewed replica refuses, it never decodes garbage;
- wire: snapshot frames ride the chunk stream only AFTER every token
  they cover, cmd kv_put preflight, cmd kv_resume streaming exactly
  the after-snapshot suffix, refusals as status-2 terminals;
- router: cadence stamping + snapshot-frame stripping is byte-
  invisible to non-resuming clients, cadence-requesting clients get
  their frames verbatim, and a SIGKILLed replica mid-relay fails over
  to a live one with the client seeing ONE unbroken bitwise-correct
  stream (zero duplicated, zero lost tokens);
- observability: ``paddle_decode_resumes_total`` outcomes, the
  ``stream_resume`` retry cause, the resume-latency histogram, and a
  zero live ``kv_snapshot`` census under the restrace sanitizer.
"""
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.inference import router as router_mod
from paddle_tpu.inference import wire_spec as ws
from paddle_tpu.inference.decode import DecodeEngine, SnapshotRefused
from paddle_tpu.inference.registry import ReplicaRegistry
from paddle_tpu.inference.router import FleetRouter
from paddle_tpu.inference.server import (_decode_arrays, _encode_arrays,
                                         _encode_deadline,
                                         _encode_decode_opts, _read_all)
from paddle_tpu.obs import prometheus as obs_prometheus
from paddle_tpu.resilience import chaos
from paddle_tpu.utils.compile_cache import compile_cache_dir

from decode_worker import reference_decode, toy_decode_model
from test_decode_serving import make_server

pytestmark = pytest.mark.decode

HID, VOCAB = 16, 32
PROMPT = np.array([1, 2, 3], np.int32)
MAX_NEW = 12


@pytest.fixture(scope="module")
def model():
    return toy_decode_model(hidden=HID, vocab=VOCAB, seed=0)


@pytest.fixture(scope="module")
def ref(model):
    return reference_decode(model, PROMPT, MAX_NEW,
                            max_seq_len=32).tolist()


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture()
def traced_resources():
    """Arm the restrace leak sanitizer for one test — the census
    assertions below check the same counters ci_gate --resources
    fails on, not hand bookkeeping."""
    from paddle_tpu.analysis import restrace

    was = restrace.enabled()
    restrace.enable(raise_on_leak=False)
    restrace.reset()
    yield restrace
    restrace.reset()
    if not was:
        restrace.disable()


def make_engine(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_seq_bucket", 8)
    kw.setdefault("watchdog_interval", 0)
    kw.setdefault("name", "decode-resume-test")
    return DecodeEngine(model, **kw)


def run_and_snapshot(engine, max_new=MAX_NEW, every=5):
    """One full decode with a snapshot cadence -> (tokens, newest
    snapshot block). Cadence 5 against MAX_NEW=12 guarantees the
    newest snapshot sits strictly BEFORE the end of the sequence."""
    req = engine.submit(PROMPT, max_new_tokens=max_new,
                        snapshot_every=every)
    toks = list(req.result(timeout=60))
    snap = req.latest_snapshot()
    assert snap is not None, "cadenced decode produced no snapshot"
    return [int(t) for t in toks], bytes(snap)


def drain(req):
    """Consume a request's stream -> the emitted token list."""
    out = []
    while True:
        toks, done = req.next_tokens(timeout=60)
        out.extend(int(t) for t in toks)
        if done:
            return out


def decode_body(prompt, max_new, snapshot_every=0, budget_ms=None,
                oneshot=False):
    body = (struct.pack("<B", 1) + _encode_arrays([prompt])
            + _encode_decode_opts(max_new, oneshot=oneshot,
                                  snapshot_every=snapshot_every))
    if budget_ms is not None:
        body += _encode_deadline(budget_ms)
    return body


def read_frames(sock, max_frames=2000):
    """-> [(status, payload bytes), ...] up to the terminal frame."""
    frames = []
    for _ in range(max_frames):
        (blen,) = struct.unpack("<I", _read_all(sock, 4))
        resp = _read_all(sock, blen)
        frames.append((resp[0], resp[1:]))
        if resp[0] != ws.STATUS_STREAM:
            return frames
    raise AssertionError("stream never terminated")


def split_stream(frames):
    """-> (terminal_status, token list, [snapshot blocks]). Token
    chunks and snapshot frames share the status-3 stream; a snapshot
    frame is self-describing by its leading magic byte."""
    tokens, snaps = [], []
    for status, payload in frames:
        if payload and ws.is_kv_snapshot(payload):
            assert status == ws.STATUS_STREAM
            snaps.append(payload)
        elif payload and status in (ws.STATUS_OK, ws.STATUS_STREAM):
            arrs = _decode_arrays(payload)
            if arrs and arrs[0].size:
                tokens.extend(int(t) for t in arrs[0])
    return frames[-1][0], tokens, snaps


def stream_request(port, body, kill_at=None):
    """Send one request body and read its whole reply stream.
    ``kill_at``: callback invoked once, as soon as the client has
    ``kill_at[0]`` tokens (mid-stream chaos injection point)."""
    n_at, hook = kill_at if kill_at else (None, None)
    fired = False
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.settimeout(240)
        s.sendall(struct.pack("<I", len(body)) + body)
        frames = []
        got = 0
        while True:
            (blen,) = struct.unpack("<I", _read_all(s, 4))
            resp = _read_all(s, blen)
            frames.append((resp[0], resp[1:]))
            if resp[1:] and not ws.is_kv_snapshot(resp[1:]) \
                    and resp[0] in (ws.STATUS_OK, ws.STATUS_STREAM):
                arrs = _decode_arrays(resp[1:])
                if arrs:
                    got += int(arrs[0].size)
            if not fired and hook is not None and got >= n_at:
                hook()
                fired = True
            if resp[0] != ws.STATUS_STREAM:
                return frames


# ------------------------------------------------------------ engine


class TestEngineSnapshotResume:
    def test_resume_suffix_bitwise_identical(self, model, ref):
        eng_a = make_engine(model)
        eng_b = make_engine(model, name="decode-resume-b")
        try:
            toks, snap = run_and_snapshot(eng_a)
            assert toks == ref
            hdr = ws.decode_kv_snapshot_header(snap)
            g = int(hdr["n_generated"])
            assert 0 < g < MAX_NEW
            req = eng_b.resume(snap, max_new_tokens=MAX_NEW)
            # the stream re-emits NOTHING before the snapshot position
            assert drain(req) == ref[g:]
            # result() sees the whole sequence including the tail
            assert [int(t) for t in req.result(timeout=60)] == ref
            st = eng_b.stats()
            assert st["resumes"]["ok"] == 1
            assert st["resumes"]["refused"] == 0
        finally:
            eng_a.close()
            eng_b.close()

    def test_snapshot_header_identity(self, model, ref):
        eng = make_engine(model)
        try:
            _, snap = run_and_snapshot(eng)
            hdr = ws.decode_kv_snapshot_header(snap)
            g = int(hdr["n_generated"])
            assert hdr["v"] == ws.KV_SNAPSHOT_VERSION
            assert hdr["prompt_len"] == PROMPT.size
            assert hdr["pos"] == PROMPT.size + g - 1
            assert hdr["last_token"] == ref[g - 1]
            assert hdr["quant"] == "f32"
            assert hdr["mesh"] == "single"
            # content identities a foreign replica compares against
            assert isinstance(hdr["fingerprint"], str) \
                and hdr["fingerprint"]
            assert isinstance(hdr["weights"], str) and hdr["weights"]
            assert eng.stats()["snapshots"] >= 1
        finally:
            eng.close()

    def test_boundary_snapshot_resumes_to_clean_finish(self, model,
                                                       ref):
        """A snapshot taken AT the resume target's stop boundary
        resumes to an immediate finish — no slot held for zero
        steps, no stream tokens."""
        eng = make_engine(model)
        try:
            _, snap = run_and_snapshot(eng)
            g = int(ws.decode_kv_snapshot_header(snap)["n_generated"])
            free_before = eng._slots.free_count()
            req = eng.resume(snap, max_new_tokens=g)
            assert drain(req) == []
            assert [int(t) for t in req.result(timeout=60)] == ref[:g]
            assert eng._slots.free_count() == free_before
        finally:
            eng.close()


class TestSkewRefusals:
    def test_weights_skew_refused(self, model):
        """Same architecture, different parameter values: the program
        fingerprint matches but the weights digest must not — a
        foreign KV cache would decode garbage."""
        eng_a = make_engine(model)
        other = toy_decode_model(hidden=HID, vocab=VOCAB, seed=1)
        eng_b = make_engine(other, name="decode-resume-skew")
        try:
            _, snap = run_and_snapshot(eng_a)
            with pytest.raises(SnapshotRefused, match="weights"):
                eng_b.resume(snap)
            assert eng_b.stats()["resumes"] == {"ok": 0, "refused": 1}
        finally:
            eng_a.close()
            eng_b.close()

    def test_fingerprint_skew_refused(self, model):
        eng_a = make_engine(model)
        other = toy_decode_model(hidden=HID, vocab=16, seed=0)
        eng_b = make_engine(other, name="decode-resume-skew2")
        try:
            _, snap = run_and_snapshot(eng_a)
            with pytest.raises(SnapshotRefused, match="fingerprint"):
                eng_b.check_snapshot(snap)
        finally:
            eng_a.close()
            eng_b.close()

    @pytest.mark.parametrize("field,value", [("quant", "w8"),
                                             ("mesh", "tp2")])
    def test_header_skew_refused(self, model, field, value):
        eng = make_engine(model)
        try:
            _, snap = run_and_snapshot(eng)
            hdr, arrays, _ = ws.decode_kv_snapshot_off(snap)
            hdr[field] = value
            tampered = ws.encode_kv_snapshot(hdr, arrays)
            with pytest.raises(SnapshotRefused, match=field):
                eng.check_snapshot(tampered)
        finally:
            eng.close()


# -------------------------------------------------------------- wire


class TestWireResume:
    def test_stream_emits_covered_snapshots_and_kv_put_ok(self, model,
                                                          ref):
        server, engine = make_server(model)
        try:
            with socket.create_connection(("127.0.0.1",
                                           server.port)) as s:
                s.sendall(struct.pack(
                    "<I", len(decode_body(PROMPT, MAX_NEW,
                                          snapshot_every=4)))
                    + decode_body(PROMPT, MAX_NEW, snapshot_every=4))
                frames = read_frames(s)
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert snaps, "cadenced stream carried no snapshot frame"
            # ordering contract: a snapshot frame arrives only after
            # every token it covers is already on the wire
            seen = 0
            for st, payload in frames:
                if payload and ws.is_kv_snapshot(payload):
                    hdr = ws.decode_kv_snapshot_header(payload)
                    assert hdr["n_generated"] <= seen
                elif payload and st in (0, ws.STATUS_STREAM):
                    arrs = _decode_arrays(payload)
                    seen += int(arrs[0].size) if arrs else 0
            # kv_put preflight: the same replica accepts its own block
            with socket.create_connection(("127.0.0.1",
                                           server.port)) as s:
                s.sendall(ws.build_request(ws.CMD_KV_PUT, snaps[-1]))
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                resp = _read_all(s, blen)
            assert resp[0] == ws.STATUS_OK
            echoed = resp[1:].decode("utf-8")
            hdr = ws.decode_kv_snapshot_header(snaps[-1])
            assert hdr["fingerprint"] in echoed
        finally:
            server.stop()

    def test_kv_resume_streams_only_the_suffix(self, model, ref):
        server_a, _ = make_server(model)
        server_b, eng_b = make_server(model)
        try:
            with socket.create_connection(("127.0.0.1",
                                           server_a.port)) as s:
                body = decode_body(PROMPT, MAX_NEW, snapshot_every=5)
                s.sendall(struct.pack("<I", len(body)) + body)
                _, _, snaps = split_stream(read_frames(s))
            snap = snaps[-1]
            g = int(ws.decode_kv_snapshot_header(snap)["n_generated"])
            assert g < MAX_NEW
            payload = (snap + _encode_decode_opts(MAX_NEW)
                       + _encode_deadline(2000.0))
            with socket.create_connection(("127.0.0.1",
                                           server_b.port)) as s:
                s.sendall(ws.build_request(ws.CMD_KV_RESUME, payload))
                status, tokens, more = split_stream(read_frames(s))
            assert (status, tokens) == (0, ref[g:])
            assert not more  # resume carried no cadence of its own
            assert eng_b.stats()["resumes"]["ok"] == 1
        finally:
            server_a.stop()
            server_b.stop()

    def test_kv_resume_oneshot_returns_full_sequence(self, model, ref):
        server_a, _ = make_server(model)
        server_b, _ = make_server(model)
        try:
            with socket.create_connection(("127.0.0.1",
                                           server_a.port)) as s:
                body = decode_body(PROMPT, MAX_NEW, snapshot_every=5)
                s.sendall(struct.pack("<I", len(body)) + body)
                _, _, snaps = split_stream(read_frames(s))
            payload = snaps[-1] + _encode_decode_opts(MAX_NEW,
                                                      oneshot=True)
            with socket.create_connection(("127.0.0.1",
                                           server_b.port)) as s:
                s.sendall(ws.build_request(ws.CMD_KV_RESUME, payload))
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                resp = _read_all(s, blen)
            assert resp[0] == ws.STATUS_OK
            toks = _decode_arrays(resp[1:])[0]
            assert [int(t) for t in toks] == ref
        finally:
            server_a.stop()
            server_b.stop()

    def test_wire_skew_refusal_is_status2_never_wrong_tokens(self,
                                                             model):
        """kv_put and kv_resume against a weights-skewed replica both
        end retryable (status 2) with ZERO token frames."""
        server_a, _ = make_server(model)
        other = toy_decode_model(hidden=HID, vocab=VOCAB, seed=1)
        server_b, _ = make_server(other)
        try:
            with socket.create_connection(("127.0.0.1",
                                           server_a.port)) as s:
                body = decode_body(PROMPT, MAX_NEW, snapshot_every=5)
                s.sendall(struct.pack("<I", len(body)) + body)
                _, _, snaps = split_stream(read_frames(s))
            snap = snaps[-1]
            with socket.create_connection(("127.0.0.1",
                                           server_b.port)) as s:
                s.sendall(ws.build_request(ws.CMD_KV_PUT, snap))
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                resp = _read_all(s, blen)
            assert resp[0] == ws.STATUS_RETRYABLE
            with socket.create_connection(("127.0.0.1",
                                           server_b.port)) as s:
                s.sendall(ws.build_request(
                    ws.CMD_KV_RESUME, snap + _encode_decode_opts(
                        MAX_NEW)))
                frames = read_frames(s)
            status, tokens, _ = split_stream(frames)
            assert (status, tokens) == (ws.STATUS_RETRYABLE, [])
        finally:
            server_a.stop()
            server_b.stop()


# ------------------------------------------------------------ router


def canonical_tokens(frames):
    """Wire-level view with the one explicitly-unpinned degree of
    freedom (chunk boundaries) normalized away: the byte-identity pin
    compares terminal status, token payload bytes, and dtype."""
    status, tokens, snaps = split_stream(frames)
    dt = None
    for st, payload in frames:
        if payload and not ws.is_kv_snapshot(payload) \
                and st in (0, ws.STATUS_STREAM):
            arrs = _decode_arrays(payload)
            if arrs:
                dt = arrs[0].dtype
    return (status, np.asarray(tokens, dt).tobytes(), str(dt),
            len(snaps))


class TestRouterByteCompat:
    @pytest.mark.parametrize("cadence", [0, 8])
    def test_non_resume_client_sees_identical_bytes(self, model, ref,
                                                    cadence):
        """The failover feature must be invisible to non-resuming
        clients: with the router stamping a cadence (and stripping
        the snapshot frames it buys) the client-visible stream is
        identical to the feature-off router — same terminal status,
        same token bytes, same dtype, and NEVER a snapshot frame."""
        server, _ = make_server(model)
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        registry.register("r1", "127.0.0.1", server.port)
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=cadence)
        try:
            deadline = time.monotonic() + 10.0
            while not registry.routable():
                assert time.monotonic() < deadline
                time.sleep(0.05)
            frames = stream_request(router.port,
                                    decode_body(PROMPT, MAX_NEW))
            assert all(not (p and ws.is_kv_snapshot(p))
                       for _, p in frames), \
                "snapshot frame leaked to a non-resuming client"
            assert canonical_tokens(frames) == (
                0, np.asarray(ref, np.int32).tobytes(), "int32", 0)
        finally:
            router.stop()
            server.stop()

    def test_cadence_requesting_client_gets_frames_verbatim(self,
                                                            model,
                                                            ref):
        """A client that asked for its own cadence owns its snapshot
        frames: the router forwards them verbatim (and still keeps a
        copy for failover)."""
        server, _ = make_server(model)
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        registry.register("r1", "127.0.0.1", server.port)
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=8)
        try:
            deadline = time.monotonic() + 10.0
            while not registry.routable():
                assert time.monotonic() < deadline
                time.sleep(0.05)
            frames = stream_request(
                router.port, decode_body(PROMPT, MAX_NEW,
                                         snapshot_every=4))
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert snaps, "client-requested snapshots were stripped"
            for snap in snaps:
                ws.decode_kv_snapshot_header(snap)  # intact blocks
        finally:
            router.stop()
            server.stop()


# ----------------------------------------------- failover end-to-end


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spawn_worker(store_dir, seed=0):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=compile_cache_dir(),
               DECODE_WORKER_HIDDEN=str(HID),
               DECODE_WORKER_VOCAB=str(VOCAB),
               DECODE_WORKER_SEED=str(seed),
               DECODE_WORKER_MAX_SLOTS="4",
               DECODE_WORKER_MAX_SEQ="32",
               DECODE_WORKER_MAX_PROMPT="8",
               PADDLE_TPU_ARTIFACT_DIR=store_dir)
    env.pop("PADDLE_TPU_SERVING_QUANT", None)
    env.pop("PADDLE_TPU_SERVING_MESH", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests",
                                      "decode_worker.py")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)
    line = proc.stdout.readline()
    assert line.startswith("PORT "), f"worker died: {line!r}"
    return proc, int(line.split()[1])


def inline_compiles(port):
    """The replica's count of inline XLA compiles (cmd-5 stats)."""
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(ws.build_request(ws.CMD_STATS))
        (blen,) = struct.unpack("<I", _read_all(s, 4))
        resp = _read_all(s, blen)
    assert resp[0] == ws.STATUS_OK
    return json.loads(resp[1:].decode())["decode"]["compiles"]


def wait_routable(registry, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while len(registry.routable()) < n:
        assert time.monotonic() < deadline, "replicas never routable"
        time.sleep(0.05)


def resume_counters():
    return {
        "ok": router_mod._M_RESUMES.value(outcome="ok"),
        "refused": router_mod._M_RESUMES.value(outcome="refused"),
        "no_snapshot": router_mod._M_RESUMES.value(
            outcome="no_snapshot"),
        "retries": router_mod._M_RETRIES.value(cause="stream_resume"),
        "latency_count": router_mod._M_RESUME_SECONDS.value()["count"],
    }


class TestRouterFailover:
    def test_sigkill_failover_bitwise_with_metrics_and_census(
            self, model, tmp_path, traced_resources):
        """The tentpole contract end-to-end over real sockets: a
        replica SIGKILLed mid-relay is invisible to the client — one
        unbroken status-0 stream, bitwise the unbroken solo decode,
        zero duplicated and zero lost tokens — while the resume
        metrics fire, the survivor absorbs the resume join on its
        warmed ladder (no inline compile) and the router's held
        snapshot is released (zero live kv_snapshot census)."""
        max_new = 16
        ref16 = reference_decode(model, PROMPT, max_new,
                                 max_seq_len=32).tolist()
        procs = {}
        procs["rA"] = spawn_worker(str(tmp_path))
        procs["rB"] = spawn_worker(str(tmp_path))
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        for rid, (_, port) in procs.items():
            registry.register(rid, "127.0.0.1", port)
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=4)
        before = resume_counters()
        killed = []

        def kill_carrier():
            rid = max(procs, key=lambda r: registry.inflight(r))
            assert registry.inflight(rid) > 0
            procs[rid][0].send_signal(signal.SIGKILL)
            killed.append(rid)

        try:
            wait_routable(registry, 2)
            warm = {rid: inline_compiles(port)
                    for rid, (_, port) in procs.items()}
            frames = stream_request(
                router.port,
                decode_body(PROMPT, max_new, budget_ms=2000.0),
                kill_at=(6, kill_carrier))
            status, tokens, snaps = split_stream(frames)
            assert killed, "kill hook never fired"
            assert status == 0, f"stream died with status {status}"
            assert tokens == ref16
            assert not snaps  # stripped: the client never opted in
            (survivor,) = set(procs) - set(killed)
            assert inline_compiles(procs[survivor][1]) == warm[survivor]
            after = resume_counters()
            assert after["ok"] - before["ok"] >= 1
            assert after["refused"] == before["refused"]
            assert after["no_snapshot"] == before["no_snapshot"]
            assert after["retries"] - before["retries"] >= 1
            assert after["latency_count"] - before["latency_count"] \
                >= 1
            text = obs_prometheus.render()
            assert 'paddle_decode_resumes_total{outcome="ok"}' in text
            assert 'paddle_fleet_retries_total{cause="stream_resume"}' \
                in text
            assert "paddle_decode_resume_seconds_count" in text
        finally:
            router.stop()
            for rid, (proc, port) in procs.items():
                proc.kill()
                proc.wait(timeout=20)
        rep = traced_resources.report()
        assert rep["census"]["kv_snapshot"] == 0, rep
        assert rep["violations"] == [], rep

    def test_death_without_snapshot_stays_retryable(self, model,
                                                    tmp_path):
        """Feature off (cadence 0, client not resuming): a mid-stream
        replica death surfaces as TODAY'S status-2 retryable terminal,
        counted as a snapshotless resume outcome."""
        proc, port = spawn_worker(str(tmp_path))
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        registry.register("r1", "127.0.0.1", port)
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=0)
        before = resume_counters()
        try:
            wait_routable(registry, 1)
            frames = stream_request(
                router.port, decode_body(PROMPT, 16, budget_ms=2000.0),
                kill_at=(3, lambda: proc.send_signal(signal.SIGKILL)))
            status, tokens, _ = split_stream(frames)
            assert status == ws.STATUS_RETRYABLE
            assert 0 < len(tokens) < 16
            after = resume_counters()
            assert after["no_snapshot"] - before["no_snapshot"] >= 1
            assert after["ok"] == before["ok"]
        finally:
            router.stop()
            proc.kill()
            proc.wait(timeout=20)
