"""The host's pauses on the span layer (tier-1, CPU): the one bridge from
``gc.callbacks`` (``obs/ledger.py bridge_gc``), its two counters, and the
span layer's lock under a callback that may interrupt its holder."""
import faulthandler
import gc
import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (installs the bridge)
from paddle_tpu import io
from paddle_tpu.obs import ledger, metrics, tracing


def _mark():
    return tracing.record_span("t.mark", 0.0).span_id


def _since(mark, name="host.gc"):
    return [s for s in tracing.finished(name=name) if s["span_id"] > mark]


def _registry():
    """{family: {generation: value}} as an operator's /metrics shows it."""
    return {f.name: {labels["generation"]: v for _, labels, v in f.samples}
            for f in metrics.REGISTRY.collect()
            if f.name.startswith("paddle_gc_")}


def test_one_callback_however_often_it_is_asked_for():
    mine = [f for f in gc.callbacks if hasattr(f, ledger._GC_MARK)]
    assert len(mine) == 1
    for _ in range(3):
        assert ledger.bridge_gc() is True
    assert [f for f in gc.callbacks if hasattr(f, ledger._GC_MARK)] == mine


def test_a_full_collection_is_one_span_under_the_callers_span():
    before, mark = ledger.gc_totals(), _mark()
    with tracing.span("t.caller") as caller:
        gc.collect()
        inside = tracing.span("t.inside")
        inside.finish()
    (found,) = _since(mark)
    assert found["attrs"]["generation"] == 2
    assert found["parent_id"] == caller.span_id
    assert found["thread"] == threading.get_ident()
    assert set(found["attrs"]) == {"generation", "collected", "uncollectable",
                                   "counts", "seconds"}
    assert caller.t0 <= found["t0"] <= found["t1"] <= inside.t0
    # it was never the ambient parent of anything
    assert inside.parent_id == caller.span_id
    after = ledger.gc_totals()
    assert after[2][0] == before[2][0] + 1
    assert after[2][1] - before[2][1] == pytest.approx(found["duration_s"],
                                                       abs=1e-3)
    # the counters as they stood when it ended ride on the span
    assert found["attrs"]["counts"][2] == after[2][0]
    assert found["attrs"]["seconds"][2] == pytest.approx(after[2][1])
    shown = _registry()
    assert shown["paddle_gc_collections_total"]["2"] == after[2][0]
    assert shown["paddle_gc_pause_seconds_total"]["2"] == after[2][1]


def test_a_young_collection_moves_the_counters_and_leaves_no_span():
    before, mark = ledger.gc_totals(), _mark()
    gc.collect(0)
    after = ledger.gc_totals()
    assert after[0][0] == before[0][0] + 1
    assert after[0][1] > before[0][1]
    assert after[1] == before[1] and after[2] == before[2]
    # a span only if this one young collection took a millisecond
    assert all(s["duration_s"] >= ledger._GC_SPAN_MIN_S
               for s in _since(mark))
    assert _registry()["paddle_gc_collections_total"]["0"] == after[0][0]


def test_a_young_collection_that_turns_out_long_is_pre_measured(monkeypatch):
    """From ``_GC_SPAN_MIN_S`` on (here 0, in a callback of its own: the
    installed one keeps the threshold it was made with)."""
    monkeypatch.setattr(ledger, "_GC_SPAN_MIN_S", 0.0)
    on_gc, mark = ledger._gc_callback(), _mark()
    info = {"generation": 1, "collected": 3, "uncollectable": 0}
    with tracing.span("t.caller") as caller:
        on_gc("start", info)
        time.sleep(0.002)
        on_gc("stop", info)
    (found,) = _since(mark)
    assert found["attrs"]["generation"] == 1
    assert found["attrs"]["collected"] == 3
    assert found["attrs"]["counts"] == [0, 1, 0]
    assert found["parent_id"] == caller.span_id
    assert found["duration_s"] >= 0.002
    assert found["t1"] - found["t0"] == pytest.approx(found["duration_s"])


def test_a_full_collection_is_in_the_profilers_trace(tmp_path):
    """A region span: ``paddle_tpu:host.gc`` on the device trace's clock,
    where ``idle_unattributed_pct`` and ``program_trace.host_spans`` read."""
    import os
    import sys

    from paddle_tpu.utils import profiler

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import program_trace, xplane

    with profiler.profiler(profile_path=str(tmp_path)):
        with tracing.span("t.traced"):
            gc.collect()
    trace = program_trace.load(xplane.find_xplane(str(tmp_path)))
    spans = {n: (s, e) for n, s, e in program_trace.host_spans(trace)}
    assert "host.gc" in spans
    assert spans["t.traced"][0] <= spans["host.gc"][0] \
        <= spans["host.gc"][1] <= spans["t.traced"][1]


def test_the_ring_survives_a_collection_at_every_allocation(monkeypatch):
    """The callback finishes ``host.gc`` on a thread that may hold the
    ring's lock further down its stack: the summary table allocates a row
    under it, and an allocation starts a collection. With a collection at
    every allocation and every collection a span (a second callback, made
    with the threshold at 0), 20,000 pre-measured and 2,000 region spans
    finish, the ring is in order and the summary table counts them all."""
    monkeypatch.setattr(ledger, "_GC_SPAN_MIN_S", 0.0)
    every = ledger._gc_callback()
    faulthandler.dump_traceback_later(30, exit=True)
    old, done = gc.get_threshold(), []

    def work():
        tracing.reset()
        for i in range(20000):
            tracing.record_span("t.pre", 0.0, i=i)
        for i in range(2000):
            with tracing.span("t.region", i=i):
                pass
        done.append(True)

    try:
        gc.callbacks.append(every)
        gc.set_threshold(1, 1, 1)
        worker = threading.Thread(target=work, daemon=True)
        worker.start()
        worker.join(35)  # hung: the dump at 30 s ends the process
    finally:
        gc.set_threshold(*old)
        gc.callbacks.remove(every)
        faulthandler.cancel_dump_traceback_later()
    assert done, "the span layer hung under its own lock"
    ring = tracing.finished()
    assert len(ring) == tracing._RING and tracing.ring_full()
    # oldest first: the order they were recorded in, whoever recorded them
    mine = [s for s in ring if s["name"] != "host.gc"]
    assert [s["span_id"] for s in mine] == sorted(s["span_id"] for s in mine)
    assert [s["attrs"]["i"] for s in mine if s["name"] == "t.region"] \
        == list(range(2000))
    ends = [s["t1"] for s in ring]
    assert all(b >= a - 1e-3 for a, b in zip(ends, ends[1:]))
    rows = {r["name"]: r["calls"] for r in tracing.summary_rows()}
    assert rows["t.pre"] == 20000 and rows["t.region"] == 2000
    assert rows["host.gc"] >= 2000 and any(s["name"] == "host.gc"
                                           for s in ring)
    tracing.reset()


def test_no_span_is_lost_when_many_threads_record_at_once():
    """``_record`` never waits for the ring's lock: a span whose thread
    did not get it stays pending until the next record or read. Sixteen
    threads on a short switch interval lose none."""
    import sys

    threads, each = 16, 2000
    old = sys.getswitchinterval()
    tracing.reset()

    def work(k):
        for i in range(each):
            tracing.record_span("t.many", 0.0, k=k, i=i)

    workers = [threading.Thread(target=work, args=(k,), daemon=True)
               for k in range(threads)]
    try:
        sys.setswitchinterval(1e-6)
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(w.is_alive() for w in workers)
    rows = {r["name"]: r["calls"] for r in tracing.summary_rows()}
    assert rows["t.many"] == threads * each
    ring = [s for s in tracing.finished() if s["name"] == "t.many"]
    assert len({s["span_id"] for s in ring}) == len(ring) >= tracing._RING - 64
    # each thread's spans are in the ring in the order it recorded them
    for k in range(threads):
        mine = [s["attrs"]["i"] for s in ring if s["attrs"]["k"] == k]
        assert mine == sorted(mine)
    tracing.reset()


class _Rows(io.Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.full((2,), i, np.float32)


def test_a_forked_worker_inherits_the_callback_and_delivers():
    loader = io.DataLoader(_Rows(), batch_size=4, num_workers=1)
    got = [np.asarray(b) for b in loader]
    assert [b[:, 0].tolist() for b in got] == [[0, 1, 2, 3], [4, 5, 6, 7]]
