"""The three readers of the host's pauses (PR 49) on recorded forms: a
synthetic ring with one stalled step of twelve, a program without the
bridge from ``gc.callbacks``, a full ring, and the toy BERT cell rehearsed
end to end on the CPU. Nothing here is a measurement."""
import json
import threading
import time

import pytest

from benchmark.harness import cells

NAMES = ("setup_gc_s", "host_gc_ms_per_step", "host_stall_ms_max")
MAIN = threading.main_thread().ident
_ids = iter(range(1, 10 ** 6))


def _sp(name, t0, t1, parent=None, thread=MAIN, **attrs):
    return {"name": name, "t0": float(t0), "t1": float(t1),
            "duration_s": float(t1 - t0), "span_id": next(_ids),
            "parent_id": parent and parent["span_id"], "trace_id": None,
            "thread": thread, "attrs": attrs}


def _ring():
    """Process start 80, window 100..101.52. Before it: a compile with a
    30 ms full collection inside its tracing, a 2 ms young one inside
    ``nn.init`` on the loader's thread, a 5 ms one inside a step call and a
    4 ms one inside no span. In it: twelve steps 100 ms apart, the seventh
    120 ms late because a full collection ran inside its ``train.step.call``;
    a step of the traced slice after it."""
    init = _sp("nn.init", 82, 84)
    spans = [
        _sp("compile.trace", 85, 88, fun="train_step", self_s=3.0),
        _sp("host.gc", 86.0, 86.03, generation=2, collected=9,
            uncollectable=0, counts=[40, 3, 1], seconds=[0.01, 0.002, 0.03]),
        _sp("compile.backend", 88, 90, fun="jit(train_step)", cache="hit"),
        init,
        _sp("host.gc", 83.0, 83.002, thread=MAIN + 1, generation=0,
            collected=0, uncollectable=0),
        _sp("train.step", 91, 92),
        _sp("host.gc", 91.5, 91.505, generation=1, collected=0,
            uncollectable=0),
        _sp("host.gc", 95.0, 95.004, generation=1, collected=0,
            uncollectable=0, counts=[900, 70, 1],
            seconds=[0.11, 0.052, 0.03]),
    ]
    t = 100.01
    for i in range(12):
        batch = _sp("io.next_batch", t - 0.008, t - 0.004)
        step = _sp("train.step", t, t + 0.006 + (0.12 if i == 6 else 0.0))
        call = _sp("train.step.call", t + 0.001, step["t1"] - 0.001, step)
        spans += [batch, _sp("io.next_batch.wait", t - 0.008, t - 0.006,
                             batch),
                  _sp("spmd.shard_batch", t - 0.004, t - 0.001), step, call]
        if i == 6:
            spans.append(_sp("host.gc", t + 0.002, t + 0.122, call,
                             generation=2, collected=0, uncollectable=0))
        t = step["t1"] + 0.094
    spans.append(_sp("train.step", 102.0, 102.1))
    return spans


def _record():
    return {"window": {"start": 100.0, "end": 101.52, "seconds": 1.52},
            "setup_s": 20.0, "spans": [("readback", 100.75, 100.80)]}


def _read(monkeypatch, capsys, spans, full=False, bridged=True):
    from paddle_tpu.obs import metrics, tracing

    monkeypatch.setattr(tracing, "finished", lambda **kw: list(spans))
    monkeypatch.setattr(tracing, "ring_full", lambda: full)
    if not bridged:  # a parent commit: no counter on the registry
        monkeypatch.setattr(metrics.REGISTRY, "collect", lambda: [])
    record = _record()
    got = {n: cells.load_module("layer_metrics", n).read(record)
           for n in NAMES}
    notes = {k: v for line in capsys.readouterr().out.splitlines()
             for k, v in json.loads(line).items()}
    return got, notes


def test_one_stalled_step_of_twelve(monkeypatch, capsys):
    got, notes = _read(monkeypatch, capsys, _ring())
    assert got["host_gc_ms_per_step"] == pytest.approx(10.0)
    assert got["host_stall_ms_max"] == pytest.approx(120.0)
    assert notes["window_gc"] == {
        "steps": 12, "spans": 1,
        "by_generation": {"2": {"n": 1, "s": 0.12, "max_ms": 120.0}}}
    stalls = notes["window_stalls"]
    assert [s["where"] for s in stalls] == [
        "interval", "interval", "interval", "median interval",
        "window start", "window end"]
    worst, median = stalls[0], stalls[3]
    assert worst["ms"] == pytest.approx(220.0)
    assert median["ms"] == pytest.approx(100.0)
    assert worst["at_s"] == pytest.approx(0.61)
    # the excess is the collection's; every other class reads as in the
    # median interval, and the classes add up to the stretch
    assert worst["ms"] - median["ms"] == pytest.approx(120.0)
    assert worst["ms_by_class"]["host.gc"] == pytest.approx(120.0)
    assert "host.gc" not in median["ms_by_class"]
    for label, ms in median["ms_by_class"].items():
        assert worst["ms_by_class"][label] == pytest.approx(ms, abs=1e-3)
    for s in stalls:
        assert sum(s["ms_by_class"].values()) == pytest.approx(s["ms"],
                                                               abs=2e-3)
        assert sum(s["uncovered_by"].values()) == pytest.approx(
            s["ms_by_class"]["uncovered"], abs=2e-3)
    assert median["ms_by_class"] == pytest.approx({
        "io.next_batch.wait": 2.0, "io.next_batch": 2.0,
        "spmd.shard_batch": 3.0, "train.step.call": 4.0,
        "train.step (self)": 2.0, "uncovered": 87.0}, abs=1e-3)
    # the two ends, whatever their length
    assert stalls[4]["ms"] == pytest.approx(10.0)
    assert stalls[5]["ms"] == pytest.approx(1520.0 - 1116.0 - 120.0, abs=0.01)
    assert stalls[5]["ms_by_class"] == {"uncovered": stalls[5]["ms"]}
    # ... and which of the benchmark's own spans the uncovered time lay in
    assert worst["uncovered_by"] == pytest.approx(
        {"bench:readback": 50.0, "none": 37.0}, abs=1e-3)
    assert median["uncovered_by"] == pytest.approx({"none": 87.0}, abs=1e-3)


def test_setup_gc_on_the_synthetic_start(monkeypatch, capsys):
    got, notes = _read(monkeypatch, capsys, _ring())
    assert got["setup_gc_s"] == pytest.approx(0.03 + 0.002 + 0.005 + 0.004)
    note = notes["setup_gc"]
    assert note["by_generation"] == {
        "0": {"n": 1, "s": 0.002, "max_ms": 2.0},
        "1": {"n": 2, "s": 0.009, "max_ms": 5.0},
        "2": {"n": 1, "s": 0.03, "max_ms": 30.0}}
    assert note["inside_s"] == pytest.approx({
        "compile.trace": 0.03, "init": 0.002, "other_spans": 0.005,
        "none": 0.004})
    assert sum(note["inside_s"].values()) == pytest.approx(
        got["setup_gc_s"], abs=1e-6)
    assert note["host_gc_spans_in_ring"] == 5
    assert note["counters_at_last_span"]["0"] == {"n": 900, "s": 0.11}


@pytest.mark.parametrize("case", ["no_bridge", "ring_full", "no_window"])
def test_nothing_to_read_is_none_from_all_three(monkeypatch, capsys, case):
    spans = _ring()
    if case == "no_bridge":
        spans = [s for s in spans if s["name"] != "host.gc"]
    if case == "no_window":
        spans = [s for s in spans if not 100 <= s["t0"] <= 101.52]
    got, notes = _read(monkeypatch, capsys, spans, full=(case == "ring_full"),
                       bridged=(case != "no_bridge"))
    if case == "no_window":  # start-up is still there to read
        assert got.pop("setup_gc_s") is not None
    assert set(got.values()) == {None}
    assert not {"window_gc", "window_stalls"} & set(notes)


def test_on_the_rehearsed_toy_bert_cell(tmp_path, capsys):
    """The train driver end to end at toy width on the CPU, then the three
    readers on its record: each reports a number, and ``setup_gc_s`` is the
    sum it prints by class."""
    from benchmark.harness import rehearsal, runner
    from benchmark.tests import toy
    from paddle_tpu.obs import tracing

    tracing.reset()  # the ring may be full of other tests' spans
    t_start = time.monotonic()
    try:
        result, _ = rehearsal.rehearse(
            "bert-base", toy.mlm(),
            cells.load_module("configs", "bert-base").TOY, str(tmp_path),
            seconds=0.5)
    finally:
        runner.stop_children()
    record = result["record"]
    record["setup_s"] = record["window"]["start"] - t_start
    capsys.readouterr()
    got = {n: cells.load_module("layer_metrics", n).read(record)
           for n in NAMES}
    notes = {k: v for line in capsys.readouterr().out.splitlines()
             for k, v in json.loads(line).items()}
    assert all(v is not None and v >= 0 for v in got.values()), got
    note = notes["setup_gc"]
    assert sum(note["inside_s"].values()) == pytest.approx(
        got["setup_gc_s"], abs=1e-6)
    assert sum(g["s"] for g in note["by_generation"].values()) \
        == pytest.approx(got["setup_gc_s"], abs=1e-5)
    assert got["setup_gc_s"] < record["setup_s"]
    assert notes["window_gc"]["steps"] == result["attempted"]
    for s in notes["window_stalls"]:
        assert sum(s["ms_by_class"].values()) == pytest.approx(s["ms"],
                                                               abs=2e-3)


def test_the_readers_are_declared_as_the_benchmark_lists_them():
    listed = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in NAMES:
        m, mod = listed[name], cells.load_module("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert m["better"] == "lower"
    assert "workloads" not in listed["setup_gc_s"]
    assert listed["setup_gc_s"]["layer"] == listed["setup_trace_s"]["layer"]
    for name in NAMES[1:]:
        assert listed[name]["workloads"] == \
            listed["host_ms_per_step"]["workloads"]
        assert listed[name]["layer"] == listed["host_ms_per_step"]["layer"]
