"""Disaggregated prefill/decode serving (ISSUE 18): phase pools with
handoff retry, pool-loss degradation, and independent autoscaling.

Layers covered here:

- routing: a pooled fleet hands prompts to the prefill pool, resumes
  the stream on the decode pool, and the client-visible byte stream is
  bitwise the colocated decode — snapshot frames never leak, short
  (max_new <= 1) requests are served by the prefill leg alone;
- chaos contract (a): a prefill replica dying mid-handoff is re-run on
  another prefill replica — the client saw nothing yet, so the stream
  is clean, with the ``handoff`` retry cause counted;
- chaos contract (b): a decode replica SIGKILLed after the handoff
  rides the PR 17 mid-stream resume path — one unbroken status-0
  stream, zero duplicated and zero lost tokens;
- chaos contract (c): a pure pool scaled or ejected to zero degrades
  to colocated serving (counted, logged, and recoverable once the pool
  comes back);
- chaos contract (d): handoff KV buffers are tracked TPU5xx resources
  — zero live ``kv_snapshot`` census after every path above;
- autoscaling: each pool's controller sees only its own pool's
  pressure (a prefill burst never scales the decode pool; decode slot
  saturation pressures only the decode pool);
- observability: ``paddle_handoff_total`` outcomes, the handoff
  latency histogram, ``paddle_fleet_pool_replicas`` gauges, and the
  ``handoff`` retry cause — over wire cmd 6 and the /metrics HTTP
  endpoint.
"""
import logging
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from paddle_tpu.inference import router as router_mod
from paddle_tpu.inference import wire_spec as ws
from paddle_tpu.inference.fleet import Autoscaler, Fleet, ReplicaHandle
from paddle_tpu.inference.registry import ReplicaRegistry
from paddle_tpu.inference.router import FleetRouter
from paddle_tpu.inference.server import _read_all
from paddle_tpu.obs import prometheus as obs_prometheus
from paddle_tpu.obs.httpd import MetricsServer
from paddle_tpu.utils.compile_cache import compile_cache_dir
from paddle_tpu.resilience import chaos

from decode_worker import reference_decode, toy_decode_model
from test_decode_resume import (decode_body, split_stream,
                                stream_request, wait_routable)
from test_decode_serving import make_server

pytestmark = pytest.mark.disagg

HID, VOCAB = 16, 32
PROMPT = np.array([1, 2, 3], np.int32)
MAX_NEW = 12
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    """Arm the restrace leak sanitizer (contract (d): the census the
    ci_gate --resources stage fails on, not hand bookkeeping)."""
    from paddle_tpu.analysis import restrace

    was = restrace.enabled()
    restrace.enable(raise_on_leak=False)
    restrace.reset()
    yield restrace
    restrace.reset()
    if not was:
        restrace.disable()


def handoff_counters():
    return {
        "ok": router_mod._M_HANDOFF.value(outcome="ok"),
        "retried": router_mod._M_HANDOFF.value(outcome="retried"),
        "degraded": router_mod._M_HANDOFF.value(outcome="degraded"),
        "failed": router_mod._M_HANDOFF.value(outcome="failed"),
        "retries": router_mod._M_RETRIES.value(cause="handoff"),
        "latency_count": router_mod._M_HANDOFF_SECONDS.value()["count"],
        "resume_ok": router_mod._M_RESUMES.value(outcome="ok"),
        "resume_retries": router_mod._M_RETRIES.value(
            cause="stream_resume"),
    }


class BrokenReplica:
    """A listener that accepts and immediately closes every
    connection — a replica dying the instant a handoff leg reaches it
    (deterministic stand-in for a SIGKILL racing the connect)."""

    # tpu-resource: acquires=router_socket
    def __init__(self):
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
                conn.close()
            except OSError:
                return

    # tpu-resource: releases=router_socket
    def close(self):
        self._stop = True
        try:
            self._sock.close()
        except OSError:
            pass


def make_pooled(model, prefill=1, decode=1, **router_kw):
    """In-process pooled topology -> (servers, registry, router).
    Replica rids sort the real replicas AFTER any planted broken ones
    (registry ties break on rid)."""
    servers = []
    registry = ReplicaRegistry(heartbeat_interval=0.1)
    for i in range(prefill):
        srv, _ = make_server(model, phase="prefill",
                             name=f"disagg-p{i}")
        servers.append(srv)
        registry.register(f"prefill-{i}", "127.0.0.1", srv.port,
                          phase="prefill")
    for i in range(decode):
        srv, _ = make_server(model, phase="decode",
                             name=f"disagg-d{i}")
        servers.append(srv)
        registry.register(f"decode-{i}", "127.0.0.1", srv.port,
                          phase="decode")
    router_kw.setdefault("snapshot_every", 4)
    # generous per-attempt timeouts: a scheduler stall on a loaded CI
    # box must never masquerade as a replica death (these tests pin
    # the NO-retry counters; retry behavior is driven by BrokenReplica
    # and SIGKILL, not by timing)
    router_kw.setdefault("handoff_timeout", 30.0)
    router_kw.setdefault("backend_timeout", 30.0)
    router = FleetRouter(registry=registry, own_registry=True,
                         **router_kw)
    wait_routable(registry, prefill + decode)
    return servers, registry, router


def stop_all(router, servers):
    router.stop()
    for s in servers:
        s.stop()


# ----------------------------------------------------------- routing


class TestDisaggRouting:
    def test_handoff_stream_bitwise_identical(self, model, ref,
                                              traced_resources):
        """The client-visible stream over a prefill->decode handoff is
        bitwise the colocated decode: same terminal, same tokens, no
        snapshot frame ever reaches the client — and the router's
        handoff snapshot buffer is released (zero live census)."""
        servers, _, router = make_pooled(model)
        before = handoff_counters()
        try:
            frames = stream_request(
                router.port, decode_body(PROMPT, MAX_NEW,
                                         budget_ms=30000.0))
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert not snaps, "snapshot frame leaked through a handoff"
            after = handoff_counters()
            assert after["ok"] - before["ok"] == 1
            assert after["latency_count"] - before["latency_count"] == 1
            assert after["retries"] == before["retries"]
            assert after["failed"] == before["failed"]
        finally:
            stop_all(router, servers)
        rep = traced_resources.report()
        assert rep["census"]["kv_snapshot"] == 0, rep
        assert rep["violations"] == [], rep

    def test_short_request_served_by_prefill_alone(self, model):
        """max_new <= 1 never leaves the prefill pool: one terminal
        status-0 frame carrying the one token (no decode leg, but the
        handoff still counts as ok)."""
        ref1 = reference_decode(model, PROMPT, 1,
                                max_seq_len=32).tolist()
        servers, _, router = make_pooled(model)
        before = handoff_counters()
        try:
            frames = stream_request(
                router.port, decode_body(PROMPT, 1, budget_ms=30000.0))
            assert len(frames) == 1
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens, snaps) == (0, ref1, [])
            after = handoff_counters()
            assert after["ok"] - before["ok"] == 1
        finally:
            stop_all(router, servers)

    def test_colocated_fleet_is_untouched(self, model, ref):
        """An all-'both' fleet never plans a handoff — the PR 15/17
        colocated path runs verbatim and no handoff counter moves."""
        server, _ = make_server(model)
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        registry.register("r1", "127.0.0.1", server.port)
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=4)
        before = handoff_counters()
        try:
            wait_routable(registry, 1)
            frames = stream_request(router.port,
                                    decode_body(PROMPT, MAX_NEW))
            status, tokens, _ = split_stream(frames)
            assert (status, tokens) == (0, ref)
            after = handoff_counters()
            assert {k: after[k] - before[k]
                    for k in ("ok", "retried", "degraded", "failed")} \
                == {"ok": 0, "retried": 0, "degraded": 0, "failed": 0}
        finally:
            router.stop()
            server.stop()

    def test_router_health_and_stats_report_pools(self, model):
        servers, _, router = make_pooled(model, prefill=1, decode=2)
        try:
            h = router.health()
            assert h["pools"] == {"prefill": 1, "decode": 2}
            assert router.stats()["pools"] == {"prefill": 1,
                                               "decode": 2}
        finally:
            stop_all(router, servers)


# -------------------------------------------- chaos (a): prefill death


class TestPrefillHandoffRetry:
    def test_dead_prefill_retried_on_another_clean_stream(
            self, model, ref, traced_resources):
        """Contract (a): the prefill replica dies mid-handoff. The
        client has seen nothing, so the router re-runs prefill on
        another prefill replica and the stream is CLEAN — not even a
        retryable terminal, and never a torn stream."""
        broken = BrokenReplica()
        servers, registry, router = make_pooled(model)
        # rid "a-dead" sorts before the real "prefill-0": the broken
        # replica is deterministically the first placement tried
        registry.register("a-dead", "127.0.0.1", broken.port,
                          phase="prefill")
        before = handoff_counters()
        try:
            frames = stream_request(
                router.port, decode_body(PROMPT, MAX_NEW,
                                         budget_ms=30000.0))
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert not snaps
            after = handoff_counters()
            assert after["retries"] - before["retries"] >= 1
            assert after["retried"] - before["retried"] == 1
            assert after["ok"] == before["ok"]
        finally:
            stop_all(router, servers)
            broken.close()
        rep = traced_resources.report()
        assert rep["census"]["kv_snapshot"] == 0, rep
        assert rep["violations"] == [], rep

    def test_armed_handoff_fault_sheds_retryable(self, model):
        """An armed chaos fault on the handoff dispatch path sheds as
        status 2 — the ok-or-retryable contract holds on the new code
        path exactly as it does on fleet.route."""
        servers, _, router = make_pooled(model)
        chaos.arm("fleet.handoff", exc=RuntimeError("chaos: handoff"))
        try:
            frames = stream_request(
                router.port, decode_body(PROMPT, MAX_NEW,
                                         budget_ms=30000.0))
            status, tokens, _ = split_stream(frames)
            assert status == ws.STATUS_RETRYABLE
            assert tokens == []
            assert chaos.visits("fleet.handoff") >= 1
        finally:
            stop_all(router, servers)


# --------------------------------------------- chaos (b): decode death


def spawn_phase_worker(store_dir, phase):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=compile_cache_dir(),
               DECODE_WORKER_HIDDEN=str(HID),
               DECODE_WORKER_VOCAB=str(VOCAB),
               DECODE_WORKER_SEED="0",
               DECODE_WORKER_MAX_SLOTS="4",
               DECODE_WORKER_MAX_SEQ="32",
               DECODE_WORKER_MAX_PROMPT="8",
               DECODE_WORKER_PHASE=phase,
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


class TestDecodeDeathRidesResume:
    @pytest.mark.slow
    def test_sigkill_decode_mid_stream_resumes_bitwise(
            self, model, tmp_path, traced_resources):
        """Contract (b) end-to-end over real processes: the decode
        replica carrying a handed-off stream is SIGKILLed mid-stream.
        The router's cadence snapshots ride the PR 17 resume path onto
        the surviving decode replica — one unbroken status-0 stream,
        bitwise the solo decode, zero duplicated, zero lost tokens."""
        max_new = 16
        ref16 = reference_decode(model, PROMPT, max_new,
                                 max_seq_len=32).tolist()
        procs = {}
        procs["p0"] = spawn_phase_worker(str(tmp_path), "prefill")
        procs["d0"] = spawn_phase_worker(str(tmp_path), "decode")
        procs["d1"] = spawn_phase_worker(str(tmp_path), "decode")
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        phases = {"p0": "prefill", "d0": "decode", "d1": "decode"}
        for rid, (_, port) in procs.items():
            registry.register(rid, "127.0.0.1", port,
                              phase=phases[rid])
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=4)
        before = handoff_counters()
        killed = []

        def kill_decode_carrier():
            rid = max(("d0", "d1"), key=registry.inflight)
            assert registry.inflight(rid) > 0, \
                "no decode replica carries the stream"
            procs[rid][0].send_signal(signal.SIGKILL)
            killed.append(rid)

        try:
            wait_routable(registry, 3)
            frames = stream_request(
                router.port,
                decode_body(PROMPT, max_new, budget_ms=30000.0),
                kill_at=(6, kill_decode_carrier))
            status, tokens, snaps = split_stream(frames)
            assert killed, "kill hook never fired"
            assert status == 0, f"stream died with status {status}"
            assert tokens == ref16
            assert not snaps
            after = handoff_counters()
            assert after["ok"] - before["ok"] == 1
            assert after["resume_ok"] - before["resume_ok"] >= 1
            assert after["resume_retries"] - before["resume_retries"] \
                >= 1
        finally:
            router.stop()
            for _, (proc, _) in procs.items():
                proc.kill()
                proc.wait(timeout=20)
        rep = traced_resources.report()
        assert rep["census"]["kv_snapshot"] == 0, rep
        assert rep["violations"] == [], rep


# --------------------------------- chaos (c): pool-loss degradation


class TestPoolLossDegradation:
    def test_decode_pool_at_zero_degrades_then_recovers(
            self, model, ref, caplog):
        """Contract (c): ejecting the decode pool to zero degrades to
        colocated serving on the surviving pool — byte-identical
        replies, counted, logged — and a replica coming back restores
        handoffs without a restart."""
        servers, registry, router = make_pooled(model)
        decode_port = servers[1].port
        before = handoff_counters()
        try:
            registry.deregister("decode-0")
            with caplog.at_level(
                    logging.WARNING,
                    logger="paddle_tpu.inference.router"):
                frames = stream_request(
                    router.port, decode_body(PROMPT, MAX_NEW,
                                             budget_ms=30000.0))
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert not snaps
            mid = handoff_counters()
            assert mid["degraded"] - before["degraded"] == 1
            assert mid["ok"] == before["ok"]
            assert any("degraded to colocated" in r.message
                       for r in caplog.records)
            # recoverable: the pool coming back restores handoffs
            registry.register("decode-0", "127.0.0.1", decode_port,
                              phase="decode")
            wait_routable(registry, 2)
            frames = stream_request(
                router.port, decode_body(PROMPT, MAX_NEW,
                                         budget_ms=30000.0))
            status, tokens, _ = split_stream(frames)
            assert (status, tokens) == (0, ref)
            after = handoff_counters()
            assert after["ok"] - mid["ok"] == 1
        finally:
            stop_all(router, servers)

    def test_decode_refusing_every_attempt_degrades_mid_stream(
            self, model, ref, caplog, traced_resources):
        """The harder half of contract (c): the decode pool exists but
        refuses every placement AFTER the first token went out. The
        stream falls back to colocated (phase-blind) serving — still
        one clean status-0 stream, counted degraded, logged — and the
        held snapshot is released on every attempt path."""
        broken = BrokenReplica()
        registry = ReplicaRegistry(heartbeat_interval=0.1)
        srv, _ = make_server(model, phase="prefill", name="disagg-pd")
        registry.register("prefill-0", "127.0.0.1", srv.port,
                          phase="prefill")
        registry.register("z-dead", "127.0.0.1", broken.port,
                          phase="decode")
        router = FleetRouter(registry=registry, own_registry=True,
                             snapshot_every=4)
        before = handoff_counters()
        try:
            wait_routable(registry, 2)
            with caplog.at_level(
                    logging.WARNING,
                    logger="paddle_tpu.inference.router"):
                frames = stream_request(
                    router.port, decode_body(PROMPT, MAX_NEW,
                                             budget_ms=30000.0))
            status, tokens, snaps = split_stream(frames)
            assert (status, tokens) == (0, ref)
            assert not snaps
            after = handoff_counters()
            assert after["degraded"] - before["degraded"] == 1
            assert after["failed"] == before["failed"]
            assert any("decode pool refused handoff" in r.message
                       for r in caplog.records)
        finally:
            router.stop()
            srv.stop()
            broken.close()
        rep = traced_resources.report()
        assert rep["census"]["kv_snapshot"] == 0, rep
        assert rep["violations"] == [], rep


# ------------------------------------------- per-pool autoscaling


def _view(rid, inflight=0, queue_depth=0, free_slots=None):
    return types.SimpleNamespace(rid=rid, inflight=inflight,
                                 queue_depth=queue_depth,
                                 free_slots=free_slots)


def fake_pooled_fleet(prefill_scaler=None, decode_scaler=None):
    """A pooled Fleet over in-process stand-in handles (nothing routes
    through them; pool membership, signals, and the supervisor tick
    are the units under test)."""
    def spawn(rid, phase):
        h = ReplicaHandle(rid, "127.0.0.1", 1)
        h._dead = False
        h.alive = lambda h=h: not h._dead
        h.stop = lambda timeout=10.0: None
        return h

    return Fleet(spawn, supervise=False, pools={
        "prefill": {"replicas": 1,
                    "autoscaler": prefill_scaler or Autoscaler(
                        min_replicas=1, max_replicas=3,
                        scale_up_pressure=4.0)},
        "decode": {"replicas": 1,
                   "autoscaler": decode_scaler or Autoscaler(
                       min_replicas=1, max_replicas=3,
                       scale_up_pressure=4.0)},
    })


class TestAutoscalerIsolation:
    def test_prefill_burst_never_scales_decode_pool(self, monkeypatch):
        """The satellite contract verbatim: admission-gate pressure
        (waiting prompts) is prefill-pool pressure. A burst of waiting
        requests scales the prefill pool up and leaves the decode pool
        alone."""
        fleet = fake_pooled_fleet()
        try:
            monkeypatch.setattr(
                fleet.router.gate, "stats",
                lambda: {"default": {"weight": 1, "waiting": 9,
                                     "granted": 0, "shed": 0}})
            views = [_view("prefill-0"), _view("decode-0",
                                               free_slots=4)]
            assert fleet.pool_signals("prefill", views=views) == (9, 0)
            assert fleet.pool_signals("decode", views=views) == (0, 0)
            tick = fleet.supervise_once()
            assert tick["pools"]["prefill"]["action"] == 1
            assert tick["pools"]["decode"]["action"] == 0
            assert len(fleet.pools()["prefill"]) == 2
            assert len(fleet.pools()["decode"]) == 1
        finally:
            fleet.close()

    def test_decode_slot_saturation_pressures_only_decode(self):
        """Decode-pool pressure is its own: zero-free-slot decode
        replicas add scale-up pressure to the decode controller and
        none to prefill."""
        fleet = fake_pooled_fleet()
        try:
            views = [_view("prefill-0", inflight=1),
                     _view("decode-0", inflight=2, free_slots=0)]
            p_wait, p_back = fleet.pool_signals("prefill", views=views)
            d_wait, d_back = fleet.pool_signals("decode", views=views)
            assert (p_wait, p_back) == (0, 1)
            assert d_wait == 0
            assert d_back >= 2 + 4.0  # backlog + saturation pressure
        finally:
            fleet.close()

    def test_dead_replica_respawns_into_its_own_pool(self):
        fleet = fake_pooled_fleet(
            prefill_scaler=Autoscaler(min_replicas=1, max_replicas=1),
            decode_scaler=Autoscaler(min_replicas=1, max_replicas=1))
        try:
            victim = fleet.pools()["decode"][0]
            fleet.handles()[victim]._dead = True
            tick = fleet.supervise_once()
            assert tick["dead"] == 1
            assert victim not in fleet.handles()
            assert len(fleet.pools()["decode"]) == 1
            assert len(fleet.pools()["prefill"]) == 1
            assert fleet.pools()["decode"][0].startswith("decode-")
        finally:
            fleet.close()


# ------------------------------------------------- observability


class TestHandoffObservability:
    def test_exposition_over_cmd6_and_http(self, model):
        """Every PR 18 series over both exposition surfaces: the
        handoff outcome counter (ok + retried + degraded all observed
        in this very test), the handoff latency histogram, the
        ``handoff`` retry cause, and the per-pool replica gauges."""
        broken = BrokenReplica()
        servers, registry, router = make_pooled(model)
        registry.register("a-dead", "127.0.0.1", broken.port,
                          phase="prefill")
        fleet = fake_pooled_fleet()
        try:
            # retried (broken prefill tried first) ...
            stream_request(router.port,
                           decode_body(PROMPT, MAX_NEW,
                                       budget_ms=30000.0))
            registry.deregister("a-dead")
            # ... ok ...
            stream_request(router.port,
                           decode_body(PROMPT, MAX_NEW,
                                       budget_ms=30000.0))
            # ... degraded ...
            registry.deregister("decode-0")
            stream_request(router.port,
                           decode_body(PROMPT, MAX_NEW,
                                       budget_ms=30000.0))
            # ... and the pool gauges via a supervisor tick
            fleet.supervise_once()

            want = [
                'paddle_handoff_total{outcome="ok"}',
                'paddle_handoff_total{outcome="retried"}',
                'paddle_handoff_total{outcome="degraded"}',
                "paddle_handoff_seconds_count",
                'paddle_fleet_retries_total{cause="handoff"}',
                'paddle_fleet_pool_replicas{phase="prefill"}',
                'paddle_fleet_pool_replicas{phase="decode"}',
            ]
            with socket.create_connection(("127.0.0.1",
                                           router.port)) as s:
                s.sendall(ws.build_request(ws.CMD_METRICS, b""))
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                resp = _read_all(s, blen)
            assert resp[0] == ws.STATUS_OK
            cmd6 = resp[1:].decode("utf-8")
            with MetricsServer() as ms:
                http = urllib.request.urlopen(
                    f"http://127.0.0.1:{ms.port}/metrics",
                    timeout=10).read().decode("utf-8")
            for needle in want:
                assert needle in cmd6, f"cmd 6 missing {needle}"
                assert needle in http, f"/metrics missing {needle}"
            # exposition format: HELP/TYPE headers on the new families
            for family, typ in [("paddle_handoff_total", "counter"),
                                ("paddle_handoff_seconds",
                                 "histogram"),
                                ("paddle_fleet_pool_replicas",
                                 "gauge")]:
                assert f"# HELP {family} " in http
                assert f"# TYPE {family} {typ}" in http
        finally:
            stop_all(router, servers)
            broken.close()
            fleet.close()
