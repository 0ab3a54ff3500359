"""Elastic pod-scale training (ISSUE 9): multi-host preemption
consensus, reshard-on-resume from multi-process staged checkpoints,
straggler/dead-host detection, and the launcher's consensus exit.

Unit tests drive the coordinator/client protocol and the host-sharded
checkpoint format in-process; the slow-marked e2e classes run real
subprocess pods through launch_collective (acceptance criteria:
4-proc pod + SIGTERM to one rank -> every rank checkpoints the SAME
consensus step and exits 143; resume onto a 2-proc mesh is
bit-identical on params/opt-state; a SIGKILL'd host triggers the
dead-host consensus instead of a hang; an injected slow host is
flagged without killing the pod).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.resilience import chaos, elastic, preemption

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(__file__), "elastic_worker.py")

FAST = {"hb_interval": 0.05, "consensus_timeout": 15.0}


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    preemption.get_preemption_handler().clear()
    yield
    chaos.reset()
    preemption.get_preemption_handler().clear()
    elastic._clear_active(elastic.active_client())


def _pod(world, dead_timeout=5.0, **coord_kw):
    coord = elastic.ElasticCoordinator(world, port=0,
                                       dead_timeout=dead_timeout,
                                       **coord_kw)
    addr = ("127.0.0.1", coord.port)
    clients = [elastic.ElasticClient(
        addr, r, world, handler=preemption.PreemptionHandler(),
        dead_timeout=dead_timeout, **FAST).start() for r in range(world)]
    return coord, clients


class TestConsensusProtocol:
    def test_consensus_is_max_step_over_ranks(self):
        coord, clients = _pod(3)
        try:
            for r, c in enumerate(clients):
                for s in range(1, 5 + r):  # ranks done 4, 5, 6
                    c.note_step(s, 0.01)
                    assert c.check_boundary(s) is None
            clients[1].request_save("maintenance")
            results = {}

            def run(r, c, done):
                results[r] = c.check_boundary(done)

            ths = [threading.Thread(target=run, args=(r, c, 4 + r))
                   for r, c in enumerate(clients)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(20)
            # every rank must save the HIGHEST boundary any rank reached
            assert results == {0: 6, 1: 6, 2: 6}
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_local_sigterm_reaches_consensus(self):
        coord, clients = _pod(2)
        try:
            for c in clients:
                c.note_step(3, 0.01)
            clients[0]._handler.request()  # the SIGTERM flag, minus signal
            results = {}

            def run(r, c):
                # a real training loop re-checks at EVERY boundary: the
                # first check may legitimately race the preempt gossip
                # and return None
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    got = c.check_boundary(3)
                    if got is not None:
                        results[r] = got
                        return
                    time.sleep(0.02)

            ths = [threading.Thread(target=run, args=(r, c))
                   for r, c in enumerate(clients)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(25)
            assert results == {0: 3, 1: 3}
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_nonblocking_mode_agrees_on_future_barrier(self):
        """Collective-training mode: proposals are fire-and-forget and
        the agreed step is max(proposals) + margin — a rank never parks
        at a boundary (which would wedge peers inside the next step's
        collective), it keeps training and stops at the future step."""
        coord = elastic.ElasticCoordinator(2, port=0, dead_timeout=5.0)
        addr = ("127.0.0.1", coord.port)
        clients = [elastic.ElasticClient(
            addr, r, 2, handler=preemption.PreemptionHandler(),
            block=False, margin=2, **FAST).start() for r in range(2)]
        try:
            for c in clients:
                c.note_step(4, 0.01)
            clients[0].request_save()
            # first boundary: both propose, nobody blocks
            assert clients[0].check_boundary(4) is None
            got = clients[1].check_boundary(4)
            # second proposal completes the round: consensus = 4 + 2
            results = set()
            if got is not None:
                results.add(got)
            for c in clients:
                c.note_step(5, 0.01)
                got = c.check_boundary(5)
                if got is not None:
                    results.add(got)
            assert results == {6}
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_dead_host_triggers_consensus_and_barrier_excludes_it(self):
        coord, clients = _pod(2, dead_timeout=0.4)
        try:
            clients[0].note_step(3, 0.01)
            # rank 1 goes silent (SIGKILL analogue): stop its heartbeats
            clients[1]._stop.set()
            clients[1]._hb_thread.join(2)
            time.sleep(0.8)
            assert clients[0].check_boundary(3) == 3
            status = clients[0].status()
            assert status["dead"] == [1]
            assert "dead_host" in status["reason"]
            clients[0].barrier("publish", timeout=5)  # must not hang
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_straggler_flagged_after_n_strikes(self):
        coord, clients = _pod(2, straggler_k=2.0, straggler_n=2)
        try:
            for s in range(1, 4):
                clients[0].note_step(s, 0.01)
                clients[0].check_boundary(s)
                clients[1].note_step(s, 0.5)
                clients[1].check_boundary(s)
            status = clients[0].status()
            assert status["stragglers"] == [1]
            assert status["ranks"]["1"]["straggler"] is True
            # flagged, never killed: no save was requested
            assert status["save"] is False
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_one_fast_step_is_not_a_straggler(self):
        coord, clients = _pod(2, straggler_k=2.0, straggler_n=3)
        try:
            # two slow strikes then recovery: strikes reset, no flag
            for dur in (0.5, 0.5, 0.01, 0.5, 0.5):
                clients[0].note_step(1, 0.01)
                clients[0].check_boundary(1)
                clients[1].note_step(1, dur)
                clients[1].check_boundary(1)
            assert clients[0].status()["stragglers"] == []
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_finished_rank_stands_as_proposal(self):
        """A rank that completed its workload must not stall a later
        consensus: its final step is a standing proposal."""
        coord, clients = _pod(2)
        try:
            for c in clients:
                c.note_step(4, 0.01)
            done = {}

            def drain(c):
                done["drain"] = c.finish_and_drain(4, timeout=15)

            t = threading.Thread(target=drain, args=(clients[0],))
            t.start()
            time.sleep(0.2)
            clients[1].request_save("late preemption")
            assert clients[1].check_boundary(4) == 4
            t.join(20)
            # the finished rank is told to join the save at its final step
            assert done["drain"] == 4
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_drain_completes_when_all_finish(self):
        coord, clients = _pod(2)
        try:
            out = {}

            def drain(r, c):
                out[r] = c.finish_and_drain(5, timeout=15)

            ths = [threading.Thread(target=drain, args=(r, c))
                   for r, c in enumerate(clients)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(20)
            assert out == {0: None, 1: None}
        finally:
            for c in clients:
                c.close()
            coord.close()

    def test_coordinator_lost_raises_instead_of_solo_save(self):
        coord, clients = _pod(2, dead_timeout=0.3)
        clients[1].close()
        coord.close()  # rank 0's process died
        c = clients[0]
        c.note_step(2, 0.01)
        c.request_save()  # swallowed: coordinator gone
        with pytest.raises(elastic.CoordinatorLost):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                c.check_boundary(2)
                time.sleep(0.05)
        c.close()

    def test_local_fallback_degrades_to_single_host(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ELASTIC_COORD", raising=False)
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "1")
        h = preemption.PreemptionHandler()
        el = elastic.init_from_env(handler=h)
        assert isinstance(el, elastic.LocalElastic)
        el.note_step(1, 0.01)
        assert el.check_boundary(1) is None
        h.request()
        assert el.check_boundary(2) == 2
        assert el.finish_and_drain(2) == 2
        el.barrier("anything")  # no-op
        el.close()

    def test_init_from_env_builds_pod(self, monkeypatch):
        from paddle_tpu.distributed.launch_mod import find_free_port

        port = find_free_port()
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_COORD",
                           f"127.0.0.1:{port}")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        el0 = elastic.init_from_env(handler=preemption.PreemptionHandler(),
                                    **FAST)
        assert el0._coordinator is not None
        assert elastic.active_client() is el0
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        el1 = elastic.init_from_env(handler=preemption.PreemptionHandler(),
                                    **FAST)
        assert el1._coordinator is None
        el1.close()
        el0.close()


class TestHostShardedFormat:
    """Multi-process staging + reshard-on-load, CPU-tested on the
    8-virtual-device mesh (xla_force_host_platform_device_count)."""

    def _state(self, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        w = np.arange(64, dtype=np.float32).reshape(8, 8)
        m = np.arange(32, dtype=np.float32).reshape(8, 4)
        sharded = jax.device_put(w, NamedSharding(mesh, P("dp")))
        repl = jax.device_put(m, NamedSharding(mesh, P()))
        return {"params": {"w": sharded, "m": repl},
                "opt_state": [sharded * 2, (repl + 1,)],
                "step": np.int64(7)}, w, m

    def _like(self, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        sds = jax.ShapeDtypeStruct
        return {"params": {
                    "w": sds((8, 8), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp"))),
                    "m": sds((8, 4), jnp.float32,
                             sharding=NamedSharding(mesh, P()))},
                "opt_state": [
                    sds((8, 8), jnp.float32,
                        sharding=NamedSharding(mesh, P("dp"))),
                    (sds((8, 4), jnp.float32,
                         sharding=NamedSharding(mesh, P())),)],
                "step": np.int64(0)}

    def test_save_then_reshard_onto_smaller_mesh_bitwise(self, tmp_path):
        import jax
        from paddle_tpu.distributed import checkpoint as dckpt
        from paddle_tpu.distributed import topology

        devs = jax.devices()
        mesh4 = topology.build_mesh(dp=4, devices=devs[:4])
        state, w, m = self._state(mesh4)
        ck = str(tmp_path / "ck")
        os.makedirs(ck)
        dckpt.write_host_shards(state, os.path.join(ck, "shard-00000"))
        dckpt.write_host_manifest(state, ck, world=1, step=7)

        mesh2 = topology.build_mesh(dp=2, devices=devs[4:6])
        out = dckpt.load_sharded(ck, self._like(mesh2))
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]), w)
        np.testing.assert_array_equal(np.asarray(out["opt_state"][0]),
                                      w * 2)
        np.testing.assert_array_equal(np.asarray(out["opt_state"][1][0]),
                                      m + 1)
        assert int(out["step"]) == 7
        # really placed on the NEW mesh with the new slice shape
        assert out["params"]["w"].addressable_shards[0].data.shape == (4, 8)

    def test_assemble_detects_missing_shard_coverage(self, tmp_path):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import checkpoint as dckpt
        from paddle_tpu.distributed import topology
        from paddle_tpu.resilience.checkpoint import CheckpointCorrupt

        devs = jax.devices()
        mesh = topology.build_mesh(dp=4, devices=devs[:4])
        w = np.arange(16, dtype=np.float32).reshape(4, 4)
        arr = jax.device_put(w, NamedSharding(mesh, P("dp")))
        ck = str(tmp_path / "ck")
        os.makedirs(ck)
        # write only HALF the shards of a 4-way-sharded leaf (the dead
        # host's shards never arrived, no survivor held them)
        dckpt.write_host_manifest({"w": arr}, ck, world=2)
        d = os.path.join(ck, "shard-00000")
        os.makedirs(d)
        entries, arrays = [], {}
        for sh in arr.addressable_shards[:2]:
            key = f"a{len(arrays)}"
            arrays[key] = np.asarray(sh.data)
            entries.append({"leaf": "w", "key": key,
                            "index": dckpt._ser_index(sh.index, arr.shape)})
        np.savez(os.path.join(d, "data.npz"), **arrays)
        with open(os.path.join(d, "index.json"), "w") as f:
            json.dump({"format": 1, "rank": 0, "entries": entries}, f)
        with pytest.raises(CheckpointCorrupt, match="covers"):
            dckpt.assemble_host_checkpoint(ck)

    def test_manager_stages_per_rank_and_rank0_commits(self, tmp_path):
        """Two 'ranks' (threads) share one root: per-rank staging,
        stage barrier, rank-0 manifest commit via os.replace, publish
        barrier — then both ranks load the same verified state."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import checkpoint as dckpt
        from paddle_tpu.distributed import topology

        devs = jax.devices()
        mesh = topology.build_mesh(dp=2, devices=devs[:2])
        w = np.arange(16, dtype=np.float32).reshape(4, 4)
        arr = jax.device_put(w, NamedSharding(mesh, P("dp")))
        state = {"params": {"w": arr}, "step": np.int64(3)}
        root = str(tmp_path / "root")
        mgrs = [dckpt.sharded_checkpoint_manager(
                    root, like=state, rank=r, world=2) for r in range(2)]
        assert all(isinstance(m, dckpt.MultiProcessShardedManager)
                   for m in mgrs)
        errs = []

        def save(r, st, step):
            try:
                mgrs[r].save(st, step)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append((r, e))

        ths = [threading.Thread(target=save, args=(r, state, 3))
               for r in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(60)
        assert not errs, errs
        assert mgrs[0].latest_step() == 3
        ckpt_dir = mgrs[0].path(3)
        assert os.path.isfile(os.path.join(ckpt_dir, "MANIFEST.json"))
        assert os.path.isfile(os.path.join(ckpt_dir, "SHARDS.json"))
        assert os.path.isdir(os.path.join(ckpt_dir, "shard-00000"))
        assert os.path.isdir(os.path.join(ckpt_dir, "shard-00001"))
        # manifest verification + assembly + placement on every rank
        for m in mgrs:
            st, step = m.load()
            assert step == 3
            np.testing.assert_array_equal(np.asarray(st["params"]["w"]), w)
        # second save: retention + LATEST move forward
        state5 = {"params": {"w": arr + 1}, "step": np.int64(5)}
        ths = [threading.Thread(target=save, args=(r, state5, 5))
               for r in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(60)
        assert not errs, errs
        assert mgrs[0].latest_step() == 5
        st, _ = mgrs[1].load()
        np.testing.assert_array_equal(np.asarray(st["params"]["w"]),
                                      w + 1)

    def test_corrupt_published_ckpt_falls_back_to_previous(self, tmp_path):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import checkpoint as dckpt
        from paddle_tpu.distributed import topology

        devs = jax.devices()
        mesh = topology.build_mesh(dp=1, devices=devs[:1])
        arr = jax.device_put(np.ones((2, 2), np.float32),
                             NamedSharding(mesh, P()))
        root = str(tmp_path / "root")
        mgr = dckpt.MultiProcessShardedManager(root, rank=0, world=1,
                                               like={"w": arr})
        mgr.save({"w": arr}, 1)
        mgr.save({"w": arr * 2}, 2)
        # corrupt the newest payload
        with open(os.path.join(mgr.path(2), "shard-00000",
                               "data.npz"), "wb") as f:
            f.write(b"garbage")
        with pytest.warns(UserWarning, match="falling back"):
            st, step = mgr.load()
        assert step == 1
        np.testing.assert_array_equal(np.asarray(st["w"]),
                                      np.ones((2, 2), np.float32))


def _launch(nproc, args, extra_env, log_dir, retries=2):
    from paddle_tpu.distributed import launch_mod

    env = {"PADDLE_TPU_ELASTIC_HB_INTERVAL": "0.1"}
    env.update(extra_env or {})
    return launch_mod.launch_collective(
        WORKER, args, nproc_per_node=nproc, log_dir=log_dir,
        extra_env=env, transient_retries=retries)


@pytest.mark.slow
@pytest.mark.chaos
class TestElasticPodE2E:
    """Subprocess acceptance: real pods through launch_collective."""

    def test_sigterm_consensus_save_then_reshard_resume_bitexact(
            self, tmp_path):
        """4-proc ZeRO-1 pod, SIGTERM to rank 1 mid-run: all ranks
        checkpoint the SAME consensus step and exit 143; a 2-proc pod
        resumes from the same sharded checkpoint (reshard-on-load),
        republishes it bit-identically, and completes."""
        from paddle_tpu.distributed import checkpoint as dckpt
        from paddle_tpu.distributed import launch_mod

        ck = str(tmp_path / "ck")
        rep = str(tmp_path / "rep")
        with pytest.raises(launch_mod.PodPreempted) as ei:
            _launch(4, [ck, rep, "12"],
                    {"PADDLE_TPU_CHAOS":
                     "site=train.step,signum=15,at=4,rank=1"},
                    str(tmp_path / "logs"))
        assert set(ei.value.codes.values()) == {143}
        reports = [json.load(open(os.path.join(rep, f"rank-{r}.json")))
                   for r in range(4)]
        steps = {r["step"] for r in reports}
        assert len(steps) == 1 and all(r["preempted"] for r in reports)
        consensus = steps.pop()
        marker = preemption.read_resume_marker(ck)
        assert marker["step"] == consensus and marker["world_size"] == 4

        # resume on HALF the slice: 2 procs, resave oracle
        resave = str(tmp_path / "resave")
        rep2 = str(tmp_path / "rep2")
        rc = _launch(2, [ck, rep2, "12"],
                     {"PADDLE_TPU_ELASTIC_RESAVE": resave},
                     str(tmp_path / "logs2"))
        assert rc == 0
        final = json.load(open(os.path.join(rep2, "rank-0.json")))
        assert final["completed"] and final["final_step"] == 12
        # the resumed pod trains only what the consensus step left:
        # a useful step is counted once across the preemption
        assert final["goodput"]["steps"] == 12 - consensus

        # bit-identity across the 4 -> 2 reshard: assemble both
        # checkpoints (pure numpy) and compare every leaf
        a, _ = dckpt.assemble_host_checkpoint(
            os.path.join(ck, f"ckpt-{consensus}"))
        b, _ = dckpt.assemble_host_checkpoint(
            os.path.join(resave, f"ckpt-{consensus}"))
        assert set(a) == set(b)
        for leaf in a:
            np.testing.assert_array_equal(a[leaf], b[leaf], err_msg=leaf)
        # the original really was multi-process sharded: rank 1's
        # opt-state shards cover a strict subset of rows
        idx = json.load(open(os.path.join(
            ck, f"ckpt-{consensus}", "shard-00001", "index.json")))
        opt_entries = [e for e in idx["entries"]
                       if e["leaf"].startswith("opt_state")
                       and e["index"]]
        assert opt_entries
        assert any(e["index"][0][0] > 0 for e in opt_entries)

    def test_sigkill_dead_host_consensus_not_hang(self, tmp_path):
        """Host loss: SIGKILL one rank of a 3-proc (collective-free)
        pod — the survivors detect the dead host, consensus-save, and
        exit 143 within the grace window instead of hanging; resume
        completes on the remaining 2 hosts."""
        from paddle_tpu.distributed import launch_mod

        ck = str(tmp_path / "ck")
        rep = str(tmp_path / "rep")
        with pytest.raises(launch_mod.PodPreempted) as ei:
            _launch(3, [ck, rep, "16", "--local"],
                    {"PADDLE_TPU_CHAOS":
                     "site=train.step,signum=9,at=4,rank=2",
                     "PADDLE_TPU_ELASTIC_DEAD_TIMEOUT": "1.0",
                     "PADDLE_TPU_ELASTIC_STEP_SLEEP": "0.15"},
                    str(tmp_path / "logs"))
        codes = ei.value.codes
        assert codes[2] == -signal.SIGKILL
        assert codes[0] == 143 and codes[1] == 143
        steps = set()
        for r in (0, 1):
            rj = json.load(open(os.path.join(rep, f"rank-{r}.json")))
            assert rj["preempted"]
            steps.add(rj["step"])
        assert len(steps) == 1
        # resume on the surviving slice shape
        rc = _launch(2, [ck, str(tmp_path / "rep2"), "16", "--local"],
                     {}, str(tmp_path / "logs2"))
        assert rc == 0

    def test_straggler_flagged_without_killing_pod(self, tmp_path):
        """A chaos-delayed rank is flagged by the coordinator within
        straggler_n steps; the pod still completes rc 0."""
        ck = str(tmp_path / "ck")
        rep = str(tmp_path / "rep")
        rc = _launch(2, [ck, rep, "8", "--local"],
                     {"PADDLE_TPU_CHAOS":
                      "site=train.step,delay=0.3,times=1000000,rank=1",
                      "PADDLE_TPU_ELASTIC_STRAGGLER_K": "2.5",
                      "PADDLE_TPU_ELASTIC_STRAGGLER_N": "2",
                      "PADDLE_TPU_ELASTIC_STEP_SLEEP": "0.02"},
                     str(tmp_path / "logs"))
        assert rc == 0
        rep0 = json.load(open(os.path.join(rep, "rank-0.json")))
        assert rep0["completed"] and rep0["final_step"] == 8
        assert rep0["stragglers"] == [1]
        # goodput ledger rode along
        assert rep0["goodput"]["steps"] == 8
        assert rep0["prometheus_goodput"]

    def test_launcher_forwards_sigterm_and_exits_143(self, tmp_path):
        """Satellite: SIGTERM aimed at the LAUNCHER is forwarded to
        every trainer; the pod consensus-saves and the launcher exits
        143 after the consensus exit (never a rank-by-rank teardown)."""
        from paddle_tpu.distributed import launch_mod

        ck = str(tmp_path / "ck")
        rep = str(tmp_path / "rep")
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   PADDLE_TPU_ELASTIC_LOCAL="1",
                   PADDLE_TPU_ELASTIC_HB_INTERVAL="0.1",
                   PADDLE_TPU_ELASTIC_STEP_SLEEP="0.1")
        proc = subprocess.Popen(
            [sys.executable, launch_mod.__file__, "--nproc_per_node", "2",
             WORKER, ck, rep, "600"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            time.sleep(6.0)  # python + jax imports, then steps underway
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=90)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == 143, out[-2000:]
        reports = [json.load(open(os.path.join(rep, f"rank-{r}.json")))
                   for r in range(2)]
        assert {r["step"] for r in reports if "step" in r} and \
            all(r.get("preempted") for r in reports)
        assert preemption.read_resume_marker(ck) is not None


class TestLauncherConsensusExit:
    def test_preempted_pod_raises_podpreempted_not_retry(self, tmp_path):
        """A script that exits 143 on every rank must surface as
        PodPreempted (and never be burned as a transient retry)."""
        from paddle_tpu.distributed import launch_mod

        script = tmp_path / "preempt.py"
        script.write_text("import sys\nsys.exit(143)\n")
        with pytest.raises(launch_mod.PodPreempted) as ei:
            launch_mod.launch_collective(str(script), [],
                                         nproc_per_node=2,
                                         log_dir=str(tmp_path / "logs"),
                                         transient_retries=3)
        assert ei.value.codes == {0: 143, 1: 143}
        # one attempt only: no retry burned on the preemption path
        logs = os.listdir(tmp_path / "logs")
        assert sorted(logs) == ["workerlog.0", "workerlog.1"]

    def test_hard_failure_during_grace_still_fails(self, tmp_path):
        from paddle_tpu.distributed import launch_mod

        script = tmp_path / "mixed.py"
        script.write_text(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "if rank == 0:\n    sys.exit(143)\n"
            "time.sleep(0.5)\nsys.exit(7)\n")
        with pytest.raises(RuntimeError, match="exited with code 7"):
            launch_mod.launch_collective(str(script), [],
                                         nproc_per_node=2)

    def test_consensus_grace_timeout_terminates(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ELASTIC_EXIT_GRACE", "1.5")
        from paddle_tpu.distributed import launch_mod

        script = tmp_path / "straggling_exit.py"
        script.write_text(
            "import os, sys, time\n"
            "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
            "if rank == 0:\n    sys.exit(143)\n"
            "time.sleep(60)\n")
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="consensus exit timed out"):
            launch_mod.launch_collective(str(script), [],
                                        nproc_per_node=2)
        assert time.monotonic() - t0 < 20

    def test_workerlogs_preserved_across_resume(self, tmp_path):
        """Satellite: relaunching into the same log_dir (the resume
        path) must not truncate the preempted incarnation's logs."""
        from paddle_tpu.distributed import launch_mod

        script = tmp_path / "talk.py"
        script.write_text("print('incarnation output', flush=True)\n")
        logs = str(tmp_path / "logs")
        launch_mod.launch_collective(str(script), [], nproc_per_node=1,
                                     log_dir=logs)
        launch_mod.launch_collective(str(script), [], nproc_per_node=1,
                                     log_dir=logs)
        names = sorted(os.listdir(logs))
        assert names == ["workerlog.0", "workerlog.0.r1"]
        for n in names:
            assert "incarnation output" in open(
                os.path.join(logs, n)).read()
