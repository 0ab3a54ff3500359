"""The reduction from a profiler trace to device metrics, on a synthetic
trace whose answers are known by construction and on a small recorded
trace (``data/tpu_v5e_bert_step_excerpt.json``: the first 150 events of
every kept line of a traced ``bert-base.train-mlm-s128`` slice on a TPU v5
lite, PR 22, names cut to 200 characters)."""
import json
import os

import pytest

from benchmark.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, async_ops=(), host=()):
    lines = [{"name": "XLA Ops", "events": [list(e) for e in ops]}]
    if async_ops:
        lines.append({"name": "Async XLA Ops",
                      "events": [list(e) for e in async_ops]})
    planes = [{"name": "/device:TPU:0", "lines": lines}]
    if host:
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host]}]})
    return {"planes": planes}


def test_merge_subtract_total():
    merged = xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [[0, 3], [5, 8]]
    assert xplane.total(merged) == 6
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert xplane.subtract([[0, 4], [6, 8]], []) == [[0, 4], [6, 8]]
    assert xplane.subtract([[0, 4]], [[0, 4]]) == []


def test_busy_is_a_union_not_a_sum():
    # two overlapping ops (1 s union) and a gap of 1 s, then 1 s more
    t = _trace([("%a = f32[] add()", 0, 1e9), ("%b = f32[] add()", 5e8, 5e8),
                ("%c = f32[] add()", 2e9, 1e9)])
    b = xplane.busy(t)
    assert b == {"busy_s": 2.0, "window_s": 3.0, "devices": 1}
    assert xplane.busy({"planes": []}) is None
    assert xplane.busy(_trace([])) is None


def test_idle_gaps_go_to_the_overlapping_host_span():
    t = _trace([("%a = f32[] add()", 0, 1e9), ("%b = f32[] add()", 3e9, 1e9),
                ("%c = f32[] add()", 5e9, 1e9)],
               host=[("bench:next_batch", 1.2e9, 1.5e9),
                     ("bench:dispatch", 2.7e9, 0.2e9),
                     ("other", 4e9, 1e9)])
    gaps = dict(xplane.idle_gaps(t))
    assert gaps == {"next_batch": 2.0, "unlabelled": 1.0}


def test_collective_total_and_exposed_part():
    # async all-reduce from 1 s to 4 s; compute covers 1..2.5; the done op
    # waits 3..4 (exposed 2.5..4 = 1.5 s, of which 0.5 s is pure gap)
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 2.5e9),
           ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(%x)", 1e9, 1e6),
           ("%all-reduce-done.1 = f32[8]{0} all-reduce-done(%s)", 3e9, 1e9),
           # an op that only CONSUMES the all-reduce is not a collective
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.1)",
            4e9, 1e9)]
    asyncs = [("%all-reduce-start.1 = f32[8]{0} all-reduce-start(%x)",
               1e9, 3e9)]
    c = xplane.collectives(_trace(ops, asyncs))
    assert c["total_s"] == pytest.approx(3.0)
    assert c["exposed_s"] == pytest.approx(1.5)
    assert c["events"] == 2
    assert xplane.collectives(_trace(ops[:1])) is None


def test_top_ops_group_the_same_operation_across_layers():
    ops = [("%fusion.399 = (bf16[2,3]{1,0:T(8,128)(2,1)}, bf16[2,3]{1,0}) "
            "fusion(f32[3]{0} %copy-done.1), kind=kOutput", 0, 3e9),
           ("%fusion.403 = (bf16[2,3]{1,0:T(8,128)(2,1)}, bf16[2,3]{1,0}) "
            "fusion(f32[3]{0} %copy-done.2), kind=kOutput", 3e9, 3e9),
           ("%copy.7 = bf16[4]{0} copy(bf16[4]{0} %x)", 6e9, 1e9)]
    assert xplane.top_ops(_trace(ops)) == [
        ["fusion (bf16[2,3], bf16[2,3])", 6.0], ["copy bf16[4]", 1.0]]
    assert xplane.op_name(ops[0][0]) == "fusion.399"
    assert not xplane.is_collective(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.1)")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "tpu_v5e_bert_step_excerpt.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_its_known_numbers(recorded):
    names = {p["name"]: {ln["name"] for ln in p["lines"]}
             for p in recorded["planes"]}
    assert {"XLA Ops", "Async XLA Ops"} <= names["/device:TPU:0"]
    b = xplane.busy(recorded)
    # the excerpt starts before the step: mostly the gap in which the host
    # dispatched it
    assert b["busy_s"] == pytest.approx(0.000312102, rel=1e-6)
    assert b["window_s"] == pytest.approx(0.002647593, rel=1e-6)
    assert 0 < b["busy_s"] <= b["window_s"]
    assert xplane.top_ops(recorded, 1)[0][0] == "convert bf16[768,768]"
    gaps = xplane.idle_gaps(recorded)
    assert gaps[0][0] == "dispatch"
    assert gaps[0][1] == pytest.approx(0.002335491, rel=1e-6)
    assert sum(g[1] for g in gaps) == pytest.approx(
        b["window_s"] - b["busy_s"], rel=1e-6)
    assert xplane.collectives(recorded) is None  # one chip: no collective


def test_recorded_dp4_trace_holds_three_exposed_all_reduces():
    """``data/tpu_v5e_bert_dp4_allreduce_excerpt.json``: device 0's op and
    async-op lines of one ``bert-base.train-mlm-s128-dp4`` step, from half a
    millisecond before its first all-reduce to half a millisecond after its
    last (TPU v5 lite x4, PR 22, names cut to 100 characters). The dp step
    reduces its gradients in three synchronous all-reduces; nothing else
    runs on the core meanwhile, so all of their time is exposed."""
    with open(os.path.join(
            DATA, "tpu_v5e_bert_dp4_allreduce_excerpt.json")) as f:
        trace = json.load(f)
    c = xplane.collectives(trace)
    assert c["events"] == 3
    assert c["total_s"] == pytest.approx(0.005435224, rel=1e-6)
    assert c["exposed_s"] == pytest.approx(c["total_s"])
    b = xplane.busy(trace)
    assert b["busy_s"] == pytest.approx(0.038517039, rel=1e-6)
    assert b["window_s"] == pytest.approx(0.038649031, rel=1e-6)
    labels = [label for label, _ in xplane.top_ops(trace, 50)]
    assert any(label.startswith("all-reduce") for label in labels)
