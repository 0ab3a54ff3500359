"""Fleet-tier suite (ROADMAP item 3): replica registry, front-tier
router, and supervisor.

Units (fast): weighted-fair-queue fairness under synthetic tenants,
the eject -> cooldown -> half-open-probe -> readmit state machine,
status-2 retry on a *different* replica, drain with zero dropped
requests, the cmd-3 ``accepting``/``draining_deadline_s`` health
fields, the MetricsServer ephemeral-port advertisement, and the
serving-goodput ledger.

Slow (``-m 'fleet and slow'``, the ci_gate --fleet stage): a real
3-subprocess-replica fleet chaos-killed mid-storm (every client reply
ok-or-retryable, goodput ledger populated, corpse respawned).
"""
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.inference.batching import RetryableError
from paddle_tpu.inference.fleet import (Autoscaler, Fleet, ReplicaHandle,
                                        subprocess_spawner)
from paddle_tpu.inference.registry import (DRAINING, EJECTED, OK, PROBING,
                                           ReplicaRegistry)
from paddle_tpu.inference.router import (FairGate, FleetRouter, ShedError,
                                         TenantPolicy, tenant_id)
from paddle_tpu.inference import wire_spec
from paddle_tpu.inference.server import (PredictorServer, _decode_arrays,
                                         _decode_request, _encode_arrays,
                                         _encode_deadline, _encode_tenant,
                                         _read_all)
from paddle_tpu.obs import goodput as obs_goodput
from paddle_tpu.obs.httpd import MetricsServer
from paddle_tpu.resilience import chaos

pytestmark = pytest.mark.fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


def _frame(arrays, *tail):
    # spec-driven frame build: the grammar (cmd byte + array block +
    # trailing fields) comes from wire_spec, not a hand-rolled pack
    return wire_spec.build_request(
        wire_spec.CMD_INFER, _encode_arrays(arrays) + b"".join(tail))


def _request(port, frame, timeout=10):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(frame)
        (blen,) = struct.unpack("<I", _read_all(s, 4))
        body = _read_all(s, blen)
    return body[0], body[1:]


def _wire_cmd(port, cmd, payload=b"", timeout=10):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        body = struct.pack("<B", cmd) + payload
        s.sendall(struct.pack("<I", len(body)) + body)
        (blen,) = struct.unpack("<I", _read_all(s, 4))
        body = _read_all(s, blen)
    return body[0], body[1:]


X = np.arange(6, dtype=np.float32).reshape(1, 6)


# ---------------------------------------------------------------- fair gate
class TestFairGate:
    def test_weighted_shares_under_saturation(self):
        """With one permit and both tenants saturating, grants follow
        the 3:1 weight ratio (SFQ's long-run share guarantee)."""
        gate = FairGate(1, policies=[TenantPolicy("heavy", weight=3),
                                     TenantPolicy("light", weight=1)])
        gate.acquire(tenant_id("heavy"), 5)  # park the single permit
        order = []

        def worker(name):
            got = gate.acquire(tenant_id(name), 30)
            order.append(got)
            gate.release()

        threads = [threading.Thread(
            target=worker, args=("heavy" if i % 2 else "light",))
            for i in range(32)]
        for t in threads:
            t.start()
        time.sleep(0.5)  # let every waiter enqueue behind the permit
        gate.release()
        for t in threads:
            t.join(30)
        # inspect the first 16 grants: heavy should get ~12 of them
        first = order[:16]
        heavy = first.count("heavy")
        assert heavy >= 2 * first.count("light"), order

    def test_full_tenant_queue_sheds_immediately_and_alone(self):
        gate = FairGate(1, policies=[TenantPolicy("noisy", weight=1,
                                                  max_queue=2),
                                     TenantPolicy("polite", weight=1,
                                                  max_queue=8)])
        gate.acquire(tenant_id("polite"), 5)  # hold the permit
        holders = []

        def parked(name):
            holders.append(gate.acquire(tenant_id(name), 20))
            gate.release()

        parked_threads = [threading.Thread(target=parked, args=("noisy",))
                          for _ in range(2)]
        for t in parked_threads:
            t.start()
        deadline = time.monotonic() + 5
        while gate.stats()["noisy"]["waiting"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        # noisy's queue (cap 2) is full: the 3rd noisy sheds NOW...
        with pytest.raises(ShedError) as ei:
            gate.acquire(tenant_id("noisy"), 5)
        assert ei.value.reason == "tenant_queue_full"
        # ...while polite still admits fine
        t_polite = threading.Thread(target=parked, args=("polite",))
        t_polite.start()
        gate.release()
        for t in parked_threads + [t_polite]:
            t.join(30)
        assert gate.stats()["noisy"]["shed"] == 1
        assert gate.stats()["polite"]["shed"] == 0

    def test_unknown_tenant_shares_default(self):
        gate = FairGate(4)
        name = gate.acquire(tenant_id("never-configured"), 1)
        assert name == "default"
        gate.release()

    def test_admission_timeout_sheds(self):
        gate = FairGate(1)
        gate.acquire(None, 5)
        t0 = time.monotonic()
        with pytest.raises(ShedError) as ei:
            gate.acquire(None, 0.2)
        assert ei.value.reason == "admission_timeout"
        assert time.monotonic() - t0 < 5
        gate.release()


# ----------------------------------------------------------- registry/probe
class TestEjectReadmit:
    def _registry(self, probe, **kw):
        kw.setdefault("heartbeat_interval", 0)  # manual ticks
        kw.setdefault("probe_cooldown", 0.1)
        kw.setdefault("eject_misses", 2)
        return ReplicaRegistry(probe_fn=probe, **kw)

    def test_io_error_ejects_cooldown_gates_probe_then_readmits(self):
        health = {"ok": True, "accepting": True,
                  "engine": {"queue_depth": 1, "declared_buckets": [1, 2]}}
        probes = []

        def probe(host, port, timeout):
            probes.append(port)
            return health

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.report_io_error("r")
            assert reg.snapshot()[0].state == EJECTED
            reg.heartbeat_once()  # cooling down: NOT probed
            assert probes == []
            assert reg.snapshot()[0].state == EJECTED
            time.sleep(0.12)
            reg.heartbeat_once()  # half-open probe -> readmit
            assert probes == [1]
            view = reg.snapshot()[0]
            assert view.state == OK
            assert view.queue_depth == 1 and view.warm_buckets == 2
        finally:
            reg.close()

    def test_failed_probe_reejects_with_fresh_cooldown(self):
        def probe(host, port, timeout):
            raise ConnectionError("still dead")

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.report_io_error("r")
            time.sleep(0.12)
            reg.heartbeat_once()  # probe fires and fails
            assert reg.snapshot()[0].state == EJECTED
            reg.heartbeat_once()  # fresh cooldown: no probe storm
            assert reg.snapshot()[0].state == EJECTED
        finally:
            reg.close()

    def test_consecutive_misses_eject(self):
        def probe(host, port, timeout):
            raise OSError("flaky")

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == OK  # one miss tolerated
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == EJECTED
        finally:
            reg.close()

    def test_replica_announced_drain_marks_draining_not_dead(self):
        def probe(host, port, timeout):
            return {"ok": False, "accepting": False, "draining": True,
                    "draining_deadline_s": 4.2, "engine": None}

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.heartbeat_once()
            view = reg.snapshot()[0]
            assert view.state == DRAINING
            assert view.draining_deadline_s == 4.2
        finally:
            reg.close()

    def test_replica_announced_drain_clears_on_accepting_heartbeat(self):
        """A drain the replica itself announced (cmd 8) must clear
        when its health says accepting again — without router
        action."""
        accepting = {"v": False}

        def probe(host, port, timeout):
            return {"ok": True, "accepting": accepting["v"],
                    "engine": None}

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == DRAINING
            accepting["v"] = True  # replica undrained itself
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == OK
        finally:
            reg.close()

    def test_router_drain_hold_survives_stale_accepting_heartbeat(self):
        """A router-initiated drain is sticky: an accepting heartbeat
        (the replica has not processed the drain yet, or a stale probe
        raced an undrain) must NOT readmit mid-drain; after the router
        lifts the hold, the next accepting heartbeat readmits."""
        def probe(host, port, timeout):
            return {"ok": True, "accepting": True, "engine": None}

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.set_draining("r", True)
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == DRAINING
            reg.set_draining("r", False)
            assert reg.snapshot()[0].state == OK
            # a stale not-accepting probe result after the undrain
            # re-marks DRAINING...
            reg._heartbeat_ok("r", OK, {"ok": True, "accepting": False})
            assert reg.snapshot()[0].state == DRAINING
            # ...but the next live accepting heartbeat recovers it
            # (no router hold remains)
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == OK
        finally:
            reg.close()

    def test_old_replica_without_accepting_field_stays_ok(self):
        """Backward compat: absent accepting/draining fields mean
        accepting."""
        def probe(host, port, timeout):
            return {"ok": True, "engine": None}

        reg = self._registry(probe)
        try:
            reg.register("r", "127.0.0.1", 1)
            reg.heartbeat_once()
            assert reg.snapshot()[0].state == OK
        finally:
            reg.close()

    def test_chaos_site_fails_heartbeat_deterministically(self):
        def probe(host, port, timeout):
            return {"ok": True, "engine": None}

        reg = self._registry(probe, eject_misses=1)
        try:
            reg.register("r", "127.0.0.1", 1)
            with chaos.fault("fleet.heartbeat", exc=OSError("injected")):
                reg.heartbeat_once()
            assert reg.snapshot()[0].state == EJECTED
        finally:
            reg.close()


# ------------------------------------------------------------------- router
def _mk_fleet_pair(run_a, run_b, tenants=(), **router_kwargs):
    """Two real PredictorServers behind a router with a tick-less
    registry (unit tests drive heartbeats manually when needed)."""
    sa = PredictorServer(run_a)
    sb = PredictorServer(run_b)
    reg = ReplicaRegistry(heartbeat_interval=0)
    reg.register("a", "127.0.0.1", sa.port)
    reg.register("b", "127.0.0.1", sb.port)
    router_kwargs.setdefault("retry_base", 0.005)
    router_kwargs.setdefault("retry_max", 0.02)
    router = FleetRouter(reg, tenants=tenants, own_registry=True,
                         **router_kwargs)
    return sa, sb, reg, router


class TestRouter:
    @pytest.mark.sharded
    def test_router_relays_sharded_replica_unmodified(self, tmp_path):
        """ISSUE 15 satellite: a SHARDED replica behind the fleet
        router answers byte-identically to a direct connection — the
        router (like the wire) is mesh-invariant, and the sharded
        replica's cmd-3 health relays its mesh descriptor through the
        fleet tier unmodified."""
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        m.eval()
        prefix = str(tmp_path / "m")
        paddle.jit.save(m, prefix,
                        input_spec=[InputSpec([None, 8], "float32")])
        env = dict(os.environ)
        env.pop("PADDLE_TPU_ARTIFACT_DIR", None)
        env.pop("PADDLE_TPU_SERVING_MESH", None)
        env.pop("PADDLE_TPU_SERVING_QUANT", None)
        worker = os.path.join(REPO, "tests", "sharded_worker.py")
        proc = subprocess.Popen(
            [sys.executable, worker, "serve", prefix, "tp2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        line = proc.stdout.readline()
        assert line.startswith("PORT "), \
            f"sharded replica failed: {line!r}\n{proc.stderr.read()[-2000:]}"
        port = int(line.split()[1])
        reg = ReplicaRegistry(heartbeat_interval=0)
        reg.register("sharded", "127.0.0.1", port)
        router = FleetRouter(reg, own_registry=True, retry_base=0.005,
                             retry_max=0.02)
        try:
            x = np.random.RandomState(5).randn(3, 8).astype(np.float32)
            frame = _frame([x])
            direct_status, direct_payload = _request(port, frame,
                                                     timeout=120)
            routed_status, routed_payload = _request(router.port, frame,
                                                     timeout=120)
            assert direct_status == routed_status == 0
            # relay is byte-exact: the router never re-encodes
            assert routed_payload == direct_payload
            # the replica's health (what the registry gossips) names
            # its mesh
            _, hp = _wire_cmd(port, wire_spec.CMD_HEALTH, timeout=120)
            assert json.loads(hp.decode())["engine"]["mesh"] == "tp2"
        finally:
            router.stop()
            try:
                _wire_cmd(port, wire_spec.CMD_STOP, timeout=10)
            except OSError:
                pass
            proc.wait(timeout=30)

    def test_retry_on_different_replica_after_shed(self):
        """Replica a sheds (status 2) every time; the router's retry
        must land on b and return ITS answer, not hammer a."""
        hits = {"a": 0, "b": 0}

        def run_a(x):
            hits["a"] += 1
            raise RetryableError("synthetic shed")

        def run_b(x):
            hits["b"] += 1
            return [x + 1.0]

        sa, sb, reg, router = _mk_fleet_pair(run_a, run_b)
        try:
            status, payload = _request(router.port, _frame([X]))
            assert status == 0
            np.testing.assert_array_equal(_decode_arrays(payload)[0],
                                          X + 1.0)
            assert hits["a"] == 1  # tried once, not hammered
            assert hits["b"] == 1
        finally:
            router.stop()
            sa.stop()
            sb.stop()

    def test_dead_replica_ejected_and_routed_around(self):
        sa, sb, reg, router = _mk_fleet_pair(lambda x: [x],
                                             lambda x: [x])
        try:
            sa.stop()  # replica a is now a dead endpoint
            for _ in range(4):
                status, _ = _request(router.port, _frame([X]))
                assert status in (0, 2)
            states = {v.rid: v.state for v in reg.snapshot()}
            assert states["a"] == EJECTED
            assert states["b"] == OK
            # traffic keeps flowing
            status, _ = _request(router.port, _frame([X]))
            assert status == 0
        finally:
            router.stop()
            sb.stop()

    def test_all_replicas_gone_is_retryable_not_error(self):
        sa, sb, reg, router = _mk_fleet_pair(lambda x: [x],
                                             lambda x: [x])
        try:
            sa.stop()
            sb.stop()
            for _ in range(3):
                status, _ = _request(router.port, _frame([X]))
                assert status == 2  # never 1, never a hang
        finally:
            router.stop()

    def test_drain_zero_drops(self):
        """Drain a replica while requests are in flight: the drain
        completes, every reply is OK, and post-drain traffic never
        touches the drained replica."""
        hits = {"a": 0, "b": 0}

        def mk(name):
            def run(x):
                hits[name] += 1
                time.sleep(0.05)
                return [x]
            return run

        sa, sb, reg, router = _mk_fleet_pair(mk("a"), mk("b"))
        statuses = []

        def client():
            status, _ = _request(router.port, _frame([X]))
            statuses.append(status)

        try:
            threads = [threading.Thread(target=client) for _ in range(8)]
            for t in threads:
                t.start()
            assert router.drain("a", deadline_s=10.0) is True
            for t in threads:
                t.join(20)
            assert statuses == [0] * 8  # zero drops, zero sheds
            a_before = hits["a"]
            for _ in range(6):
                status, _ = _request(router.port, _frame([X]))
                assert status == 0
            assert hits["a"] == a_before  # drained replica untouched
            states = {v.rid: v.state for v in reg.snapshot()}
            assert states["a"] == DRAINING
            # the replica itself announces the drain (cmd 8 round-trip)
            _, hbody = _wire_cmd(sa.port, 3)
            health = json.loads(hbody)
            assert health["accepting"] is False
            router.undrain("a")
            assert {v.rid: v.state
                    for v in reg.snapshot()}["a"] == OK
            _, hbody = _wire_cmd(sa.port, 3)
            assert json.loads(hbody)["accepting"] is True
        finally:
            router.stop()
            sa.stop()
            sb.stop()

    def test_chaos_route_fault_sheds_instead_of_erroring(self):
        sa, sb, reg, router = _mk_fleet_pair(lambda x: [x],
                                             lambda x: [x])
        try:
            with chaos.fault("fleet.route", exc=RuntimeError("injected")):
                status, _ = _request(router.port, _frame([X]))
                assert status == 2  # ok-or-retryable, never status 1
            status, _ = _request(router.port, _frame([X]))
            assert status == 0
        finally:
            router.stop()
            sa.stop()
            sb.stop()

    def test_per_tenant_accounting_in_ledger(self):
        obs_goodput.SERVING_LEDGER.reset()
        sa, sb, reg, router = _mk_fleet_pair(
            lambda x: [x], lambda x: [x],
            tenants=[TenantPolicy("t1", weight=2)])
        try:
            f1 = _frame([X], _encode_deadline(5000),
                        _encode_tenant(tenant_id("t1")))
            for _ in range(3):
                status, _ = _request(router.port, f1)
                assert status == 0
            rep = obs_goodput.SERVING_LEDGER.report()
            assert rep["tenants"]["t1"]["ok"] == 3
            assert rep["tenants"]["t1"]["deadline_hit_rate"] == 1.0
            assert rep["goodput"] > 0
        finally:
            router.stop()
            sa.stop()
            sb.stop()


# ----------------------------------------------------- server drain fields
class TestHealthDrainFields:
    def test_cmd8_drain_and_undrain_roundtrip(self):
        srv = PredictorServer(lambda x: [x])
        try:
            _, body = _wire_cmd(srv.port, 8, struct.pack("<d", 6.5))
            h = json.loads(body)
            assert h["accepting"] is False and h["draining"] is True
            assert 0 < h["draining_deadline_s"] <= 6.5
            # a draining server still serves what it receives
            status, _ = _request(srv.port, _frame([X]))
            assert status == 0
            _, body = _wire_cmd(srv.port, 8, struct.pack("<d", -1.0))
            h = json.loads(body)
            assert h["accepting"] is True
            assert h["draining_deadline_s"] is None
        finally:
            srv.stop()

    def test_stop_sets_drain_fields(self):
        srv = PredictorServer(lambda x: [x])
        srv.stop()
        h = json.loads(srv._health_json())
        assert h["accepting"] is False and h["draining"] is True

    def test_absent_fields_mean_accepting(self):
        """The registry treats pre-PR-11 health JSON (no accepting /
        draining_deadline_s) as accepting — pinned here so the wire
        stays backward compatible."""
        srv = PredictorServer(lambda x: [x])
        try:
            _, body = _wire_cmd(srv.port, 3)
            h = json.loads(body)
            assert h["accepting"] is True
            assert h["draining_deadline_s"] is None
        finally:
            srv.stop()


# -------------------------------------------------------------- wire tenant
class TestTenantWire:
    def test_fields_after_tenant_still_parse(self):
        """A replica must skip the tenant field so a deadline BEHIND
        it still parses (routers strip it, but direct clients may
        not)."""
        payload = (_encode_arrays([X]) + _encode_tenant(7)
                   + _encode_deadline(123.0))
        arrays, budget, trace, _dec = _decode_request(payload)
        np.testing.assert_array_equal(arrays[0], X)
        assert budget == pytest.approx(0.123)

    def test_tenant_id_stable(self):
        assert tenant_id("polite") == tenant_id("polite")
        assert tenant_id("polite") != tenant_id("noisy")

    def test_router_strips_tenant_but_keeps_other_fields(self):
        """_split_meta must cut the trailing fields OUT of
        arrays_bytes so the router forwards deadline/trace WITHOUT the
        tenant marker — a pre-tenant replica would stop parsing at the
        unknown marker and lose every field behind it."""
        from paddle_tpu.inference.router import _split_meta

        arrays = _encode_arrays([X])
        body = (struct.pack("<B", 1) + arrays + _encode_tenant(7)
                + _encode_deadline(250.0))
        arrays_bytes, fields, tail, tid, budget, trace = \
            _split_meta(body)
        assert arrays_bytes == struct.pack("<B", 1) + arrays
        assert tail == b""
        assert tid == 7 and budget == pytest.approx(0.25)
        markers = [m for m, _raw in fields]
        assert set(markers) == {0x7E, 0xDD}
        # the forwarded reassembly (what _dispatch builds) parses on a
        # tenant-unaware server with the deadline intact
        fwd = (arrays_bytes
               + b"".join(struct.pack("<B", m) + raw
                          for m, raw in fields if m != 0x7E))
        _arr, fwd_budget, _tr, _dec = _decode_request(fwd[1:])
        assert fwd_budget == pytest.approx(0.25)


# ------------------------------------------------------------- metrics port
class TestMetricsServerPort:
    def test_port_zero_reports_bound_port(self):
        ms = MetricsServer(0)
        try:
            assert ms.port > 0
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ms.port}/metrics",
                    timeout=5) as r:
                assert r.status == 200
                assert b"paddle" in r.read()
        finally:
            ms.close()

    def test_registry_advertises_metrics_endpoint(self):
        """The bound ephemeral port flows registry-through so scrapers
        can discover the whole fleet's /metrics endpoints."""
        ms = MetricsServer(0)
        reg = ReplicaRegistry(heartbeat_interval=0)
        try:
            reg.register("r", "127.0.0.1", 12345,
                         metrics_port=ms.port)
            view = reg.snapshot()[0]
            assert view.metrics_port == ms.port
            assert view.as_dict()["metrics_port"] == ms.port
        finally:
            reg.close()
            ms.close()


# ----------------------------------------------------------- goodput ledger
class TestServingGoodput:
    def test_report_shape_and_math(self):
        led = obs_goodput.ServingGoodput(export=False,
                                         accountant=obs_goodput
                                         .GoodputAccountant(export=False))
        led.record("a", "ok", 3.0)
        led.record("a", "shed", 1.0)
        led.record("b", "late", 1.0)
        rep = led.report()
        assert rep["goodput"] == pytest.approx(0.6)
        assert rep["tenants"]["a"]["deadline_hit_rate"] == 0.5
        assert rep["tenants"]["b"]["late"] == 1
        assert rep["replies"] == 3
        led.reset()
        assert led.report()["replies"] == 0

    def test_unknown_outcome_raises(self):
        with pytest.raises(ValueError):
            obs_goodput.ServingGoodput(export=False).record("t", "nope")

    def test_serving_category_in_accountant(self):
        acct = obs_goodput.GoodputAccountant(export=False)
        acct.account("serving", 1.5)
        assert acct.report()["serving_s"] == 1.5


# -------------------------------------------------------------- autoscaler
class TestAutoscaler:
    def test_decisions(self):
        a = Autoscaler(min_replicas=1, max_replicas=3,
                       scale_up_pressure=4.0, scale_down_ticks=2)
        assert a.decide(0, 0, 0) == 1  # heal to min
        assert a.decide(1, waiting=8, backlog=0) == 1  # pressure
        assert a.decide(3, waiting=50, backlog=50) == 0  # at max
        assert a.decide(2, 0, 0) == 0  # idle tick 1
        assert a.decide(2, 0, 0) == -1  # idle tick 2 -> shrink
        assert a.decide(1, 0, 0) == 0  # never below min
        a2 = Autoscaler(min_replicas=1, max_replicas=3,
                        scale_down_ticks=2)
        assert a2.decide(2, 0, 0) == 0
        assert a2.decide(2, waiting=1, backlog=0) == 0  # busy resets
        assert a2.decide(2, 0, 0) == 0  # idle count restarted

    def test_fleet_respawns_dead_replica(self):
        """Supervisor tick replaces a replica whose handle reports
        dead (in-process stand-ins; the subprocess + SIGKILL version
        is the slow e2e)."""
        servers = []

        def spawn(rid):
            srv = PredictorServer(lambda x: [x])
            servers.append(srv)
            h = ReplicaHandle(rid, "127.0.0.1", srv.port)
            h._dead = False
            h.alive = lambda h=h: not h._dead
            h.stop = lambda timeout=10.0, s=srv: s.stop()
            return h

        fleet = Fleet(spawn, replicas=2, supervise=False,
                      autoscaler=Autoscaler(min_replicas=2,
                                            max_replicas=2))
        try:
            victim_rid = sorted(fleet.handles())[0]
            fleet.handles()[victim_rid]._dead = True
            tick = fleet.supervise_once()
            assert tick["dead"] == 1
            assert fleet.respawns == 1
            assert len(fleet.handles()) == 2
            assert victim_rid not in fleet.handles()
            status, _ = _request(fleet.port, _frame([X]))
            assert status == 0
        finally:
            fleet.close()
            for s in servers:
                s.stop()


# ------------------------------------------------------------------ slow e2e
def _save_tiny_model(prefix):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec

    paddle.seed(0)

    class Tiny(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(6, 6)

        def forward(self, x):
            return self.fc(x)

    m = Tiny()
    m.eval()
    paddle.jit.save(m, prefix,
                    input_spec=[InputSpec([None, 6], "float32")])


@pytest.mark.slow
class TestFleetChaosE2E:
    def test_sigkill_one_of_three_mid_storm(self, tmp_path):
        """The acceptance storm in miniature: 3 subprocess replicas,
        2 tenants, one replica SIGKILLed mid-storm. Every reply must
        be ok-or-retryable, the goodput ledger must be populated, and
        the supervisor must respawn the corpse."""
        import signal

        prefix = str(tmp_path / "tiny")
        _save_tiny_model(prefix)
        obs_goodput.SERVING_LEDGER.reset()
        spawn = subprocess_spawner(
            prefix,
            extra_env={"JAX_PLATFORMS": "cpu",
                       "PADDLE_TPU_ARTIFACT_DIR":
                           str(tmp_path / "store")})
        fleet = Fleet(
            spawn, replicas=3,
            tenants=[TenantPolicy("noisy", weight=1, max_queue=8),
                     TenantPolicy("polite", weight=4)],
            autoscaler=Autoscaler(min_replicas=3, max_replicas=3),
            supervise_interval=0.2,
            router_kwargs={"retry_base": 0.01, "retry_max": 0.1,
                           "retry_attempts": 4})
        statuses = []
        statuses_lock = threading.Lock()
        stop_ev = threading.Event()

        def client(tenant, deadline_ms):
            tail = [_encode_tenant(tenant_id(tenant))]
            if deadline_ms:
                tail.insert(0, _encode_deadline(deadline_ms))
            frame = _frame([X], *tail)
            while not stop_ev.is_set():
                status, payload = _request(fleet.port, frame,
                                           timeout=60)
                assert status in (0, 2), f"forbidden status {status}"
                if status == 0:
                    out = _decode_arrays(payload)[0]
                    assert out.shape == (1, 6)  # never wrong tensors
                with statuses_lock:
                    statuses.append(status)

        try:
            threads = ([threading.Thread(target=client,
                                         args=("noisy", None))
                        for _ in range(4)]
                       + [threading.Thread(target=client,
                                           args=("polite", 5000.0))
                          for _ in range(2)])
            for t in threads:
                t.start()
            time.sleep(1.0)  # storm warms up
            victim_rid, victim = sorted(fleet.handles().items())[0]
            os.kill(victim.pid, signal.SIGKILL)
            time.sleep(4.0)  # storm rides through the kill + respawn
            stop_ev.set()
            for t in threads:
                t.join(60)
            with statuses_lock:
                seen = list(statuses)
            assert seen, "storm produced no replies"
            assert set(seen) <= {0, 2}
            assert seen.count(0) > 0
            # respawn lands (spawn may outlast the storm)
            t_end = time.monotonic() + 120
            while time.monotonic() < t_end:
                if fleet.respawns >= 1 and len(fleet.handles()) == 3:
                    break
                time.sleep(0.2)
            assert fleet.respawns >= 1
            assert len(fleet.handles()) == 3
            rep = obs_goodput.SERVING_LEDGER.report()
            assert rep["replies"] > 0 and rep["goodput"] > 0
            assert rep["tenants"]["polite"]["ok"] > 0
            # post-chaos: the fleet still answers
            status, _ = _request(fleet.port, _frame([X]))
            assert status == 0
        finally:
            stop_ev.set()
            fleet.close()
