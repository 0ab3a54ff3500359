"""The readers of what the program says about itself (PR 23): the wire
reader against ``jax.profiler.ProfileData`` on a trace taken here, the
scope parser on the ``op_name``s jax writes, the nine per-layer readers on
hand-built spans and on a recorded trace
(``data/tpu_v5e_bert_step_scoped_excerpt.json``: every 20th operation of
the ``XLA Ops`` line of a traced ``bert-base.train-mlm-s128`` slice on a TPU
v5 lite, PR 23, taken with ``program_trace.excerpt``: names cut to 100
characters, each with its ``op_name``; the host lines' ``paddle_tpu:*``
spans whole), and ``BENCHMARK.json`` against the accepted one it grew from."""
import json
import os

import pytest

from benchmark.harness import cells, program_trace, xplane
from benchmark.tests.test_extensible import _appended_only

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("host_ms_per_step", "loader_wait_ms_per_step", "device_scoped_pct",
       "fwd_ms_per_step", "bwd_ms_per_step", "optimizer_ms_per_step",
       "norm_ms_per_step", "attention_ms_per_step", "idle_unattributed_pct")
DEVICE = NEW[2:]


def _reader(name):
    return cells.load_module("layer_metrics", name)


def test_wire_reader_agrees_with_profile_data(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from paddle_tpu.obs import tracing
    from paddle_tpu.utils import profiler

    with profiler.profiler(profile_path=str(tmp_path)):
        with tracing.span("t.outer"):
            for _ in range(3):
                with tracing.span("t.inner"):
                    jax.jit(jnp.sin)(jnp.ones(8)).block_until_ready()
    path = xplane.find_xplane(str(tmp_path))
    want = sorted(
        (e.name, e.start_ns, e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(program_trace.SPAN_PREFIX))
    trace = program_trace.load(path)
    got = sorted(tuple(e) for plane in trace["planes"]
                 for line in plane["lines"] for e in line["events"])
    assert [n for n, _, _ in got] == ["paddle_tpu:t.inner"] * 3 + [
        "paddle_tpu:t.outer"]
    for (n0, s0, d0), (n1, s1, d1) in zip(got, want):
        assert n0 == n1
        assert s0 == pytest.approx(s1, abs=1.0)   # ProfileData rounds to ns
        assert d0 == pytest.approx(d1, abs=1.0)
    assert [n for n, _, _ in program_trace.host_spans(trace)].count(
        "t.inner") == 3


@pytest.mark.parametrize("op_name, phase, innermost, classes", [
    ("jit(train_step)/jvp(PackedMLM)/inner:BertForPretraining/bert:BertModel"
     "/encoder:TransformerEncoder/3:TransformerEncoderLayer/norm1:LayerNorm"
     "/jit(_var)/div", "fwd", "LayerNorm",
     ["PackedMLM", "BertForPretraining", "BertModel", "TransformerEncoder",
      "TransformerEncoderLayer", "LayerNorm"]),
    ("jit(train_step)/transpose(jvp(PackedMLM))/inner:BertForPretraining/"
     "bert:BertModel/encoder:TransformerEncoder/0:TransformerEncoderLayer/"
     "self_attn:MultiHeadAttention/out_proj:Linear/dot_general", "bwd",
     "Linear", None),
    ("jit(train_step)/jvp(FromUint8)/net:ResNet/layer1:Sequential/"
     "0:BottleneckBlock/bn1:BatchNorm2D/reduce_sum", "fwd", "BatchNorm2D",
     None),
    ("jit(train_step)/jvp(loss)/jit(log_softmax)/reduce_max", "fwd", "",
     []),
    ("jit(train_step)/transpose(jvp(loss))/mul", "bwd", "", []),
    ("jit(train_step)/clip/sqrt", "clip", "", []),
    ("jit(train_step)/optimizer/mul", "optimizer", "", []),
    # no program scope: a helper program, XLA's own copy, a jitted function
    # with a capital, a primitive
    ("jit(_threefry_fold_in)/jit(Foo)/shift_left", None, "", []),
    ("", None, "", []),
])
def test_scope_of(op_name, phase, innermost, classes):
    scope = program_trace.scope_of(op_name)
    assert scope["phase"] == phase
    assert program_trace.innermost(scope) == innermost
    if classes is not None:
        assert [c for _, c in scope["modules"]] == classes


# ------------------------------------------------- the two span readers
def _span(i, name, t0, t1, thread=1, parent=None):
    return {"name": name, "t0": t0, "t1": t1, "duration_s": t1 - t0,
            "span_id": i, "parent_id": parent, "trace_id": None,
            "thread": thread, "attrs": {}}


def test_span_readers_take_the_trainers_thread_in_the_window(monkeypatch):
    from paddle_tpu.obs import tracing

    spans = [
        _span(1, "train.step", 9.0, 9.5),                 # before the window
        _span(2, "io.next_batch", 10.0, 10.002),
        _span(3, "io.next_batch.wait", 10.0, 10.001, parent=2),
        _span(4, "io.next_batch", 10.0, 10.150, thread=2),  # reader thread:
        _span(5, "io.next_batch.wait", 10.0, 10.100, thread=2,  # not the
              parent=4),                                        # trainer
        _span(6, "spmd.shard_batch", 10.002, 10.003),
        _span(7, "spmd.shard_batch", 10.003, 10.004),
        _span(8, "train.step", 10.004, 10.010),
        _span(9, "train.step.call", 10.005, 10.009, parent=8),
        _span(10, "train.step", 10.2, 10.204),
        _span(11, "train.step", 21.0, 21.1)]              # after it
    monkeypatch.setattr(tracing, "finished", lambda: spans)
    record = {"window": {"start": 10.0, "end": 20.0, "seconds": 10.0}}
    # (2 + 1 + 1 + 6 + 4) ms over the program's own two steps
    assert _reader("host_ms_per_step").read(record) == pytest.approx(7.0)
    assert _reader("loader_wait_ms_per_step").read(record) == \
        pytest.approx(0.5)
    split = program_trace.span_split(record)
    assert split["steps"] == 2
    assert split["ms_per_step"]["train.step (self)"] == pytest.approx(3.0)
    assert split["ms_per_step"]["train.step.call"] == pytest.approx(2.0)
    # the parent's span layer: durations only, nothing to place in a window
    monkeypatch.setattr(tracing, "finished", lambda: [
        {"name": "train.step", "duration_s": 0.1, "span_id": 1,
         "parent_id": None, "trace_id": None, "attrs": {}}])
    for name in NEW[:2]:
        assert _reader(name).read(record) is None


# ------------------------------------------- the seven device readers
def _op(op_name, start_ms, ms):
    return ["%x = f32[] fusion()", start_ms * 1e6, ms * 1e6, op_name]


def _record(ops, host=()):
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": ops}]}]
    if host:
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["paddle_tpu:" + n, s * 1e6, d * 1e6] for n, s, d in host]}]})
    return {"program_trace": {"planes": planes}, "trace_steps": 2}


ROOT = "jit(train_step)/jvp(Net)/"
BACK = "jit(train_step)/transpose(jvp(Net))/"


def test_device_readers_on_a_synthetic_slice():
    record = _record([
        _op(ROOT + "attn:MultiHeadAttention/dot_general", 0, 4),
        _op(ROOT + "attn:MultiHeadAttention/out_proj:Linear/dot_general",
            4, 2),
        _op(ROOT + "norm:LayerNorm/jit(_var)/div", 6, 2),
        _op("jit(train_step)/jvp(loss)/reduce_sum", 8, 1),
        _op("", 9, 1),                                     # XLA's own copy
        # idle 10..14
        _op(BACK + "attn:MultiHeadAttention/transpose", 14, 6),
        _op(BACK + "bn:BatchNorm2D/mul", 20, 4),
        _op("jit(train_step)/clip/sqrt", 24, 1),
        _op("jit(train_step)/optimizer/mul", 25, 3)],
        host=[("train.step", 9.5, 1.5), ("io.next_batch", 10.5, 1.0)])
    got = {name: _reader(name).read(record) for name in DEVICE}
    assert got == pytest.approx({
        "device_scoped_pct": 100.0 * 23 / 24,
        "fwd_ms_per_step": 9 / 2, "bwd_ms_per_step": 10 / 2,
        "optimizer_ms_per_step": 4 / 2, "norm_ms_per_step": 6 / 2,
        "attention_ms_per_step": 10 / 2,
        # of the 4 idle ms the program's spans cover 10..11.5
        "idle_unattributed_pct": 100.0 * 2.5 / 4})
    assert got["fwd_ms_per_step"] + got["bwd_ms_per_step"] \
        + got["optimizer_ms_per_step"] <= 24 / 2
    assert program_trace.scope_table(record, 2) == [
        ["bwd attn:MultiHeadAttention", 3.0],
        ["fwd attn:MultiHeadAttention", 2.0]]


def test_device_readers_find_nothing_in_a_scopeless_trace(monkeypatch):
    """The parent's program (or an executable from a compile cache written
    before the scopes): nothing to read, except that ``device_scoped_pct``
    says 0 where the program does scope its operations."""
    record = _record([_op("jit(step)/jit(main)/mul", 0, 4),
                      _op("", 4, 1)])
    for name in DEVICE:
        want = 0.0 if name == "device_scoped_pct" else None
        assert _reader(name).read(record) == want, name
    monkeypatch.setattr(program_trace, "program_has_scopes", lambda: False)
    assert _reader("device_scoped_pct").read(record) is None
    assert _reader("attention_ms_per_step").read(_record([
        _op(ROOT + "bn:BatchNorm2D/mul", 0, 1)])) is None


def test_readers_on_the_recorded_v5e_slice():
    with open(os.path.join(DATA,
                           "tpu_v5e_bert_step_scoped_excerpt.json")) as f:
        trace = json.load(f)
    record = {"program_trace": trace, "trace_steps": 6}
    ops = program_trace.ops(record)
    busy = xplane.total(xplane.merge((s, e) for _, s, e in ops)) / 6e6
    got = {name: _reader(name).read(record) for name in DEVICE}
    assert all(v is not None for v in got.values())
    assert 90.0 < got["device_scoped_pct"] <= 100.0
    assert got["bwd_ms_per_step"] > got["fwd_ms_per_step"] > 0
    assert got["fwd_ms_per_step"] + got["bwd_ms_per_step"] \
        + got["optimizer_ms_per_step"] <= busy
    assert 0 < got["norm_ms_per_step"] < got["attention_ms_per_step"] \
        < got["fwd_ms_per_step"] + got["bwd_ms_per_step"]
    assert 0.0 <= got["idle_unattributed_pct"] <= 100.0
    names = {n for n, _, _ in program_trace.host_spans(trace)}
    assert {"train.step", "train.step.call", "io.next_batch",
            "io.next_batch.wait", "spmd.shard_batch"} <= names
    labels = [label for label, _ in program_trace.scope_table(record)]
    assert "bwd self_attn:MultiHeadAttention" in labels
    assert "fwd linear1:Linear" in labels


def test_benchmark_json_only_grew():
    """Against the accepted benchmark PR 23 started from (PR 22's
    ``BENCHMARK.json``, kept as data): every list starts with the old
    entries unchanged, and PR 23's nine metrics follow them."""
    with open(os.path.join(DATA, "benchmark_pr22.json")) as f:
        accepted = json.load(f)
    bench = cells.load_benchmark()
    _appended_only(accepted, bench)
    added = bench["per_layer"][len(accepted["per_layer"]):][:len(NEW)]
    assert [m["name"] for m in added] == list(NEW)
    train = [w["name"] for w in accepted["workloads"]]
    for m in added:
        assert m["moves"] == "train_samples_per_s"
        want = ([w for w in train if w.startswith("bert-base.")]
                if m["name"] == "attention_ms_per_step" else train)
        assert m["workloads"][:len(want)] == want  # later cells append
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
