"""The span layer's contract (tier-1, CPU): spans carry start and end on
``time.monotonic`` and nest by the ambient parent; the train path leaves
its spans where its host work happens; the step program's operations carry
their module and phase in ``op_name``; tracing has no switch."""
import inspect
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import io, nn, optimizer
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_KEYS = {"name", "t0", "t1", "duration_s", "span_id", "parent_id",
             "trace_id", "thread", "attrs"}


def _since(mark):
    """Finished spans recorded after ``mark`` (a span id)."""
    return [s for s in tracing.finished() if s["span_id"] > mark]


def _mark():
    return tracing.record_span("t.mark", 0.0).span_id


def _children(spans, parent):
    return [s["name"] for s in spans if s["parent_id"] == parent["span_id"]]


# ------------------------------------------------------------ the layer
def test_span_dict_is_on_time_monotonic():
    mark = _mark()
    before = time.monotonic()
    with tracing.span("t.clock", rows=3):
        time.sleep(0.01)
    after = time.monotonic()
    (sp,) = [s for s in _since(mark) if s["name"] == "t.clock"]
    assert set(sp) == SPAN_KEYS
    assert before <= sp["t0"] < sp["t1"] <= after
    assert sp["duration_s"] == sp["t1"] - sp["t0"] >= 0.01
    assert sp["thread"] == threading.get_ident()
    assert sp["attrs"] == {"rows": 3} and sp["parent_id"] is None


def test_spans_nest_by_the_ambient_parent():
    mark = _mark()
    with tracing.span("t.a") as a:
        with tracing.span("t.b") as b:
            tracing.record_span("t.pre", 0.002)
            handle = tracing.start_span("t.handle")
        with tracing.span("t.c"):
            pass
        handle.finish()
    by = {s["name"]: s for s in _since(mark)}
    assert by["t.a"]["parent_id"] is None
    assert by["t.b"]["parent_id"] == by["t.c"]["parent_id"] == a.span_id
    assert by["t.pre"]["parent_id"] == by["t.handle"]["parent_id"] \
        == b.span_id
    # a handle is not an ambient parent: only ``with`` installs one
    assert by["t.pre"]["t1"] - by["t.pre"]["t0"] == pytest.approx(0.002)
    with tracing.span("t.after"):
        pass
    assert _since(mark)[-1]["parent_id"] is None


def test_cross_thread_finish_keeps_the_explicit_parent():
    mark = _mark()
    with tracing.span("t.handler") as handler:
        pass

    def scheduler(sp):
        with tracing.span("t.scheduler"):
            sp.finish(rows=2)  # inside another thread's ambient span

    sp = tracing.start_span("t.queue", parent_id=handler.span_id)
    th = threading.Thread(target=scheduler, args=(sp,))
    th.start()
    th.join(10)
    assert not th.is_alive()
    by = {s["name"]: s for s in _since(mark)}
    assert by["t.queue"]["parent_id"] == handler.span_id
    assert by["t.queue"]["thread"] == threading.get_ident()
    assert by["t.scheduler"]["thread"] != threading.get_ident()
    assert by["t.queue"]["attrs"] == {"rows": 2}


def test_self_times_on_a_hand_built_tree():
    def sp(i, parent, t0, t1):
        return {"span_id": i, "parent_id": parent, "t0": t0, "t1": t1}

    spans = [sp(1, None, 0.0, 10.0),
             sp(2, 1, 1.0, 4.0),      # children 2 and 3 overlap on [3, 4]
             sp(3, 1, 3.0, 6.0),
             sp(4, 1, 9.0, 12.0),     # runs past its parent: clipped at 10
             sp(5, 2, 1.5, 2.0),
             sp(6, 99, 0.0, 1.0)]     # its parent fell out of the ring
    got = tracing.self_times(spans)
    assert got == pytest.approx({1: 10.0 - (5.0 + 1.0), 2: 2.5, 3: 3.0,
                                 4: 3.0, 5: 0.5, 6: 1.0})


def test_region_spans_reach_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData

    from paddle_tpu.utils import profiler

    with profiler.profiler(profile_path=str(tmp_path)):
        with profiler.RecordEvent("t.outer"):
            with tracing.span("t.inner"):
                jnp.ones(8).block_until_ready()
    (path,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(tracing.ANNOTATION_PREFIX)]
    by = {e.name: e for e in events}
    assert set(by) == {"paddle_tpu:t.outer", "paddle_tpu:t.inner"}
    outer, inner = by["paddle_tpu:t.outer"], by["paddle_tpu:t.inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns \
        <= outer.start_ns + outer.duration_ns
    # the operator's trace is not buried under Python frames
    assert sum(len(list(line.events)) for plane in
               ProfileData.from_file(str(path)).planes
               for line in plane.lines) < 5000


def test_the_ring_is_bounded_and_there_is_no_switch():
    source = inspect.getsource(tracing)
    assert "environ" not in source and "getenv" not in source
    assert tracing._finished.maxlen == tracing._RING >= 4096
    for _ in range(tracing._RING + 10):
        tracing.record_span("t.fill", 0.0)
    assert len(tracing.finished()) == tracing._RING


def test_obs_imports_and_records_without_jax():
    script = (
        "import sys, types\n"
        "sys.modules['jax'] = None  # 'import jax' now raises ImportError\n"
        f"pkg = types.ModuleType('paddle_tpu'); "
        f"pkg.__path__ = [{os.path.join(REPO, 'paddle_tpu')!r}]\n"
        "sys.modules['paddle_tpu'] = pkg\n"
        "from paddle_tpu import obs\n"
        "with obs.span('t.nojax') as sp:\n"
        "    obs.tracing.record_span('t.child', 0.001)\n"
        "(a, b) = obs.tracing.finished()\n"
        "assert b['name'] == 't.nojax' and a['parent_id'] == b['span_id']\n"
        "assert sp._ann is None\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


# ------------------------------------------------------- the train path
class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4)
        self.bn = nn.BatchNorm1D(4)

    def forward(self, x):
        return self.bn(self.fc(x))


def _mse(out, y):
    return jnp.mean((out - y) ** 2)


def test_step_fn_leaves_one_train_step_with_four_children_a_call():
    net = _Net()
    opt = optimizer.SGD(0.1, parameters=net.parameters())
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step_fn, init_fn = spmd.build_train_step(net, _mse, opt, mesh=mesh)
    params, state = init_fn()
    x = spmd.shard_batch(np.ones((4, 8), np.float32), mesh)
    y = spmd.shard_batch(np.zeros((4, 4), np.float32), mesh)
    mark = _mark()
    for _ in range(3):
        _, params, state = step_fn(params, state, x, y)
    spans = _since(mark)
    steps = [s for s in spans if s["name"] == "train.step"]
    assert len(steps) == 3
    for st in steps:
        assert _children(spans, st) == [
            "train.step.lr", "train.step.buffers_in", "train.step.call",
            "train.step.buffers_out"]
        kids = [s for s in spans if s["parent_id"] == st["span_id"]]
        assert all(st["t0"] <= k["t0"] <= k["t1"] <= st["t1"] for k in kids)
        assert tracing.self_times(spans)[st["span_id"]] >= 0.0
    (buffers_in,) = {s["attrs"]["buffers"] for s in spans
                     if s["name"] == "train.step.buffers_in"}
    assert buffers_in == len(net.functional_state()[1]) > 0


def test_shard_batch_leaves_its_span():
    mesh = topology.build_mesh(dp=2, devices=jax.devices()[:2])
    mark = _mark()
    spmd.shard_batch(np.ones((4, 8), np.float32), mesh)
    (sp,) = [s for s in _since(mark) if s["name"] == "spmd.shard_batch"]
    assert sp["attrs"] == {"devices": 2, "bytes": 4 * 8 * 4}


class _Rows(io.Dataset):
    def __len__(self):
        return 32

    def __getitem__(self, i):
        return np.full((6,), i, np.float32), np.int32(i)


@pytest.mark.parametrize("num_workers", [0, 2])
def test_dataloader_leaves_its_spans(num_workers):
    mark = _mark()
    loader = io.DataLoader(_Rows(), batch_size=8, num_workers=num_workers)
    batches = list(loader)
    assert len(batches) == 4
    spans = _since(mark)
    me = threading.get_ident()
    mine = [s for s in spans
            if s["name"] == "io.next_batch" and s["thread"] == me]
    # the consumer's side: blocked on the buffered reader, nothing else
    full = [s for s in mine if "bytes" in s["attrs"]]
    assert len(full) == 4
    for s in full:
        assert s["attrs"] == {"source": "prefetch",
                              "bytes": 8 * 6 * 4 + 8 * 4}
        assert _children(spans, s) == ["io.next_batch.wait"]
    # the producer's side, on the reader's thread
    source = "workers" if num_workers else "dataset"
    made = [s for s in spans if s["name"] == "io.next_batch"
            and s["attrs"].get("source") == source]
    assert len(made) == 4 and all(s["thread"] != me for s in made)
    for s in made:
        assert s["attrs"]["workers"] == num_workers
        assert s["attrs"]["bytes"] == 8 * 6 * 4 + 8 * 4
        kids = _children(spans, s)
        assert kids[-1] == "io.next_batch.convert"
        assert ("io.next_batch.wait" in kids) == bool(num_workers)
    produced = [s for s in spans if s["name"] == "io.worker.produce"]
    assert len(produced) == (4 if num_workers else 0)
    for s in produced:
        assert s["attrs"]["worker"] in range(num_workers)
        assert s["attrs"]["seconds"] == pytest.approx(s["duration_s"])


# -------------------------------------------------- scopes in the program
_SCOPE = re.compile(r"(?:^|[/(])(?:[\w.\-]*:)?[A-Z]\w*\)*(?:/|$)")
_PHASES = ("loss", "clip", "optimizer")


def _toy_bert():
    from paddle_tpu.text.models import BertForPretraining

    class Packed(nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, packed):
            logits, _ = self.inner(packed[:, :16],
                                   masked_positions=packed[:, 16:])
            return logits

    paddle.seed(0)
    model = BertForPretraining(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=32)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    x = np.concatenate([np.ones((2, 16), np.int32),
                        np.arange(4, dtype=np.int32)[None].repeat(2, 0)], 1)
    return Packed(model), loss, opt, x, np.ones((2, 4), np.int32)


def _toy_resnet():
    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    net = resnet18(num_classes=10)
    opt = optimizer.Momentum(0.1, parameters=net.parameters(),
                             grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def loss(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    return (net, loss, opt, np.ones((2, 3, 32, 32), np.float32),
            np.ones((2,), np.int32))


@pytest.mark.parametrize("build, wanted", [
    (_toy_bert, ("LayerNorm", "self_attn:MultiHeadAttention",
                 "linear1:Linear", "transpose(jvp(Packed))")),
    (_toy_resnet, ("BatchNorm2D", "Conv2D", "transpose(jvp(ResNet))")),
], ids=["bert", "resnet"])
def test_step_program_carries_module_and_phase_scopes(build, wanted):
    """The program jax hands to XLA (StableHLO with its locations: what
    the program controls; XLA turns a location's name into the HLO
    ``op_name``, and a backend's own rewrites may drop it): every module
    and phase is named, on at least 95% of the operations that do work."""
    layer, loss, opt, x, y = build()
    layer.train()
    mesh = topology.build_mesh(dp=1, devices=jax.devices()[:1])
    step_fn, init_fn = spmd.build_train_step(layer, loss, opt, mesh=mesh,
                                             amp_level="O1")
    params, state = init_fn()
    text = step_fn.jitted.lower(
        params, state, layer.functional_state()[1], x, y,
        jax.random.PRNGKey(0), jnp.float32(0.1)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    # the entry function: a jitted helper (``_var``, ``_where``) is lowered
    # once as a private function and takes its scope from each call site
    main = text[text.index("func.func public @main"):]
    main = main[:re.search(r"^  \}", main, re.M).start()]
    names = [locs[m.group(1)] for line in main.splitlines()
             if " = stablehlo." in line and "stablehlo.constant" not in line
             for m in [re.search(r"loc\((#loc\d+)\)$", line)]
             if m and m.group(1) in locs]
    assert len(names) > 500
    for scope in wanted + tuple(f"/{p}/" for p in ("clip", "optimizer")) \
            + ("jvp(loss)",):
        assert any(scope in n for n in names), scope
    scoped = [n for n in names
              if _SCOPE.search(n) or any(f"/{p}/" in n + "/" or
                                         f"({p})" in n for p in _PHASES)]
    assert len(scoped) >= 0.95 * len(names), (
        len(scoped), len(names),
        sorted(set(names) - set(scoped))[:10])


def test_eager_call_enters_no_scope(monkeypatch):
    def boom(name):
        raise AssertionError(f"eager dispatch entered named_scope({name!r})")

    monkeypatch.setattr(jax, "named_scope", boom)
    net = _Net()
    out = net(paddle.to_tensor(np.ones((4, 8), np.float32)))
    assert out.shape == [4, 4]
    assert net.fc.scope_name() == "fc:Linear"
    assert net.scope_name() == "_Net"
