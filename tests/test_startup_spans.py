"""Start-up on the span layer (tier-1, CPU): the region spans where start-up
work happens, the one bridge from ``jax.monitoring`` (``obs/ledger.py``),
and the five ``setup_*`` readers of the benchmark on synthetic records."""
import json
import os
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
from paddle_tpu.distributed import comm_opt, pipeline, spmd, topology
from paddle_tpu.obs import ledger, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.harness import cells  # noqa: E402


def _mark():
    return tracing.record_span("t.mark", 0.0).span_id


def _since(mark, name=None):
    return [s for s in tracing.finished() if s["span_id"] > mark
            and (name is None or s["name"] == name)]


def _mse(out, y):
    return jnp.mean((out - y) ** 2)


# ---------------------------------------------------------- region spans
@pytest.mark.parametrize("make, initializer, nbytes", [
    (lambda: nn.Linear(8, 4, bias_attr=False), "XavierNormal", 8 * 4 * 4),
    (lambda: nn.LayerNorm(6), "Constant", 6 * 4),
    (lambda: nn.Embedding(10, 3), "Normal", 10 * 3 * 4),
])
def test_nn_init_is_one_span_a_parameter_drawn(make, initializer, nbytes):
    mark = _mark()
    with tracing.span("t.build") as build:
        layer = make()
    found = _since(mark, "nn.init")
    assert len(found) == len(layer.parameters())
    first = found[0]
    assert first["attrs"]["bytes"] == nbytes
    assert first["attrs"]["initializer"] == initializer or (
        initializer == "Normal"
        and first["attrs"]["initializer"].endswith("Normal"))
    assert {s["parent_id"] for s in found} == {build.span_id}
    assert first["thread"] == threading.get_ident()


def test_nn_init_leaves_no_span_inside_a_traced_program():
    """Only where values are really drawn: a layer built while jax traces
    draws tracers (or constants of the program), not start-up work."""
    from paddle_tpu.core import dispatch

    mark = _mark()
    with dispatch.trace_mode():
        nn.Linear(4, 4)
    assert _since(mark, "nn.init") == []


def _pipeline_parts():
    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(16, 16)

        def forward(self, x):
            return paddle.tanh(self.fc(x))

    return [nn.Linear(8, 16)], [Block() for _ in range(4)], [nn.Linear(16, 4)]


def _build(builder):
    """(step_fn, init_fn, x, y) of one builder on a small model."""
    devs = jax.devices()
    if builder == "pipeline":
        pre, blocks, post = _pipeline_parts()
        opt = optimizer.SGD(0.1, parameters=[
            p for l in pre + blocks + post for p in l.parameters()])
        mesh = topology.build_mesh(dp=1, pp=4, devices=devs[:4])
        step, init = pipeline.build_pipeline_train_step(
            pre, blocks, post, _mse, opt, mesh=mesh, num_micro=4)
        return step, init, np.ones((8, 8), np.float32), \
            np.ones((8, 4), np.float32)
    model = nn.Sequential(*[nn.Linear(16, 16) for _ in range(3)])
    opt = optimizer.Adam(1e-3, parameters=model.parameters())
    build = {"spmd": spmd.build_train_step,
             "fsdp": spmd.build_fsdp_train_step,
             "localsgd": comm_opt.build_localsgd_train_step}[builder]
    mesh = topology.build_mesh(dp=2, devices=devs[:2])
    step, init = build(model, _mse, opt, mesh=mesh)
    x = np.ones((4, 16), np.float32)
    return step, init, x, x


@pytest.mark.parametrize("builder, children", [
    ("spmd", True), ("fsdp", True), ("pipeline", True), ("localsgd", False)])
def test_builders_span_their_build_and_their_state(builder, children):
    """``train.build_step`` once a builder call, ``train.init_state`` once
    an ``init_fn`` call with the arrays it placed; the two phases are its
    children where the builder has them (LocalSGD places a parameter and
    its optimizer state in one loop: no children, no invented names)."""
    mark = _mark()
    step, init, x, y = _build(builder)
    (built,) = _since(mark, "train.build_step")
    assert built["attrs"] == {"builder": builder}
    assert _since(mark, "train.init_state") == []

    mark = _mark()
    params, opt_state = init()
    (state,) = _since(mark, "train.init_state")
    leaves = jax.tree_util.tree_leaves((params, opt_state))
    assert state["attrs"] == {"leaves": len(leaves),
                              "bytes": sum(a.nbytes for a in leaves)}
    kids = {s["name"]: s for s in _since(mark)
            if s["parent_id"] == state["span_id"]
            and s["name"].startswith("train.")}
    if not children:
        assert kids == {}
        return
    assert set(kids) == {"train.init_state.params",
                         "train.init_state.opt_state"}
    p, o = kids["train.init_state.params"], kids["train.init_state.opt_state"]
    assert p["attrs"]["leaves"] == len(params)
    assert p["attrs"]["leaves"] + o["attrs"]["leaves"] == len(leaves)
    assert p["attrs"]["bytes"] + o["attrs"]["bytes"] \
        == state["attrs"]["bytes"]
    assert state["t0"] <= p["t0"] <= p["t1"] <= o["t0"] <= o["t1"] \
        <= state["t1"]


def test_a_compile_inside_the_first_step_descends_from_its_call():
    step, init, x, y = _build("spmd")
    params, opt_state = init()
    mark = _mark()
    _, params, opt_state = step(params, opt_state, x, y)
    spans = _since(mark)
    by_id = {s["span_id"]: s for s in spans}

    def lineage(s):
        names = []
        while s["parent_id"] in by_id:
            s = by_id[s["parent_id"]]
            names.append(s["name"])
        return names

    backends = [s for s in spans if s["name"] == "compile.backend"
                and s["attrs"]["fun"] == "jit(train_step)"]
    assert len(backends) == 1
    assert lineage(backends[0]) == ["train.step.call", "train.step"]
    (traced,) = [s for s in spans if s["name"] == "compile.trace"
                 and s["attrs"]["fun"] == "train_step"]
    assert lineage(traced) == ["train.step.call", "train.step"]
    # the second call of the same step (no buffers) compiles nothing
    mark = _mark()
    step(params, opt_state, x, y)
    assert [s for s in _since(mark, "compile.backend")
            if s["attrs"]["fun"] == "jit(train_step)"] == []


class _Rows(io.Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.full((2,), i, np.float32)


@pytest.mark.parametrize("workers, buffered", [(0, True), (0, False),
                                               (1, True)])
def test_loader_start_is_one_span_an_iterator(workers, buffered):
    loader = io.DataLoader(_Rows(), batch_size=4, num_workers=workers,
                           use_buffer_reader=buffered)
    mark = _mark()
    batches = iter(loader)
    (start,) = _since(mark, "io.loader.start")
    assert start["attrs"] == {"workers": workers, "buffered": buffered}
    assert start["parent_id"] is None
    assert len(list(batches)) == 2
    assert len(_since(mark, "io.loader.start")) == 1


# -------------------------------------------------------------- the bridge
def _monitoring_listeners():
    from jax._src import monitoring

    return [f for group in (monitoring._event_listeners,
                            monitoring._event_duration_secs_listeners,
                            monitoring._scalar_listeners)
            for f in group
            if getattr(f, "__module__", "") == "paddle_tpu.obs.ledger"]


def test_one_listener_set_however_often_it_is_asked_for():
    assert len(_monitoring_listeners()) == 3
    for _ in range(3):
        assert ledger.bridge_jax_monitoring() is True
    assert len(_monitoring_listeners()) == 3


def test_a_jit_compile_is_one_trace_lower_backend_triple():
    def tripled(x):
        return x * 3 + 1

    counts = {k: ledger._COMPILES.value(kind=k)
              for k in ("trace", "lower", "backend")}
    mark = _mark()
    with tracing.span("t.caller") as caller:
        jax.jit(tripled)(jnp.arange(7.0))
    mine = [s for s in _since(mark) if s["name"].startswith("compile.")
            and "tripled" in s["attrs"]["fun"]]
    assert [s["name"] for s in mine] == ["compile.trace", "compile.lower",
                                         "compile.backend"]
    trace, lower, backend = mine
    assert trace["attrs"]["fun"] == "tripled"
    assert lower["attrs"] == {"fun": "jit(tripled)"}
    assert backend["attrs"]["fun"] == "jit(tripled)"
    assert backend["attrs"]["cache"] in ("uncached", "written", "hit")
    assert trace["attrs"]["self_s"] == pytest.approx(trace["duration_s"])
    # pre-measured spans that end at the callback, under the caller's span
    assert trace["t1"] <= lower["t0"] + 1e-3 and lower["t1"] <= backend["t1"]
    assert {s["parent_id"] for s in mine} == {caller.span_id}
    assert all(s["thread"] == threading.get_ident() for s in mine)
    for kind, was in counts.items():
        assert ledger._COMPILES.value(kind=kind) >= was + 1
    # a cached call of the same program leaves nothing
    mark = _mark()
    jax.jit(tripled)(jnp.arange(7.0))
    assert [s for s in _since(mark) if s["name"].startswith("compile.")
            and "tripled" in s["attrs"]["fun"]] == []


@pytest.mark.parametrize("inner_sleep_s, inner_is_a_span", [
    (3 * ledger._NESTED_TRACE_MIN_S, True), (0.0, False)])
def test_self_s_of_a_jit_that_calls_a_jit(monkeypatch, inner_sleep_s,
                                          inner_is_a_span):
    """jax's trace regions nest. A nested region long enough to be a span
    of its own is taken out of the outer span's ``self_s``; a short one
    (every jitted jax.numpy function a program calls) stays in it and out
    of the ring. Either way the ``self_s`` add up to the outermost
    region's seconds."""
    if not inner_is_a_span:  # "short" is not the loaded machine's to say
        monkeypatch.setattr(ledger, "_NESTED_TRACE_MIN_S", float("inf"))
    @jax.jit
    def nested_inner(x):
        time.sleep(inner_sleep_s)
        return x + 2

    @jax.jit
    def nesting_outer(x):
        time.sleep(0.02)
        return nested_inner(x) * 2

    mark = _mark()
    nesting_outer(jnp.ones(3))
    traces = {s["attrs"]["fun"]: s for s in _since(mark, "compile.trace")
              if s["attrs"]["fun"].startswith("nest")}
    outer = traces["nesting_outer"]
    assert outer["duration_s"] >= 0.02 + inner_sleep_s
    if inner_is_a_span:
        inner = traces["nested_inner"]
        assert inner["attrs"]["self_s"] == pytest.approx(inner["duration_s"])
        assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
        assert outer["attrs"]["self_s"] == pytest.approx(
            outer["duration_s"] - inner["duration_s"])
    else:
        assert "nested_inner" not in traces
        assert outer["attrs"]["self_s"] == pytest.approx(outer["duration_s"])
    assert sum(s["attrs"]["self_s"] for s in traces.values()) \
        == pytest.approx(outer["duration_s"])


def test_helpers_a_lowering_rule_traces_are_no_spans(monkeypatch):
    """The threefry lowering traces ``add`` / ``bitwise_xor`` helpers by the
    hundred, 0.1 ms each: they stay in the ``compile.lower`` span they run
    in, so a parameter draw leaves one trace span a program. Which nested
    region is short is the clock's to say, and a loaded machine's clock says
    10 ms of a helper now and then (the driver's whole run, PR 48): here no
    nested region is long enough, so only the fold is under test; and the
    program is this call's own, so no earlier test has compiled it."""
    monkeypatch.setattr(ledger, "_NESTED_TRACE_MIN_S", float("inf"))

    def drawn_here(key):
        return jax.random.normal(key, (3, 11))

    mark = _mark()
    jax.jit(drawn_here)(jax.random.PRNGKey(1))
    found = _since(mark)
    traced = [s["attrs"]["fun"] for s in found if s["name"] == "compile.trace"]
    lowered = [s["attrs"]["fun"] for s in found
               if s["name"] == "compile.lower"]
    assert "jit(drawn_here)" in lowered
    assert [f"jit({f})" for f in traced] == lowered


_CHILD = r"""
import importlib, json, os, sys, time
import jax, jax.numpy as jnp
import paddle_tpu
from paddle_tpu.obs import ledger, tracing

def labels(threshold_s):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", threshold_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    def fn(x):
        return jnp.sin(x) * threshold_s + 5
    fn.__name__ = "labelled_%d" % int(threshold_s)
    out = []
    for _ in range(2):
        mark = tracing.record_span("t.mark", 0.0).span_id
        jax.jit(fn)(jnp.ones(5))
        out += [s["attrs"] for s in tracing.finished(name="compile.backend")
                if s["span_id"] > mark and "labelled" in s["attrs"]["fun"]]
        jax.clear_caches()
    return out

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
report = {"at_0s": labels(0.0), "at_1s": labels(1.0)}

# re-imported and reloaded, the listeners stay one set
importlib.reload(ledger)
importlib.reload(paddle_tpu)
for name in [m for m in sys.modules if m.startswith("paddle_tpu.obs")]:
    del sys.modules[name]
import paddle_tpu.obs.ledger as again
again.bridge_jax_monitoring()
again.bridge_gc()
import gc
report["gc_callbacks"] = len([f for f in gc.callbacks
                              if hasattr(f, again._GC_MARK)])
before = again.gc_totals()[2][0]
gc.collect()
report["gc_counted_by_the_new_copy"] = again.gc_totals()[2][0] - before
from jax._src import monitoring
report["listeners"] = [len([f for f in group if getattr(f, "__module__", "")
                            == "paddle_tpu.obs.ledger"])
                       for group in (monitoring._event_listeners,
                                     monitoring._event_duration_secs_listeners,
                                     monitoring._scalar_listeners)]
print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def child_report(tmp_path_factory):
    """One child process (its own jax config and compile cache): what the
    bridge says of the persistent cache, and how many listeners it has
    after reloads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD,
         str(tmp_path_factory.mktemp("compile_cache"))],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("threshold, first, second", [
    ("at_0s", "written", "hit"), ("at_1s", "uncached", "uncached")])
def test_backend_spans_say_what_the_cache_did(child_report, threshold, first,
                                              second):
    """With the cache's threshold at 0 the first compile is written and
    the second start reads it (``read_s`` on the hit); at the program's
    own 1 s a small program is compiled at every start: ``uncached``."""
    one, two = child_report[threshold]
    assert (one["cache"], two["cache"]) == (first, second)
    assert ("read_s" in two) == (second == "hit")
    assert "read_s" not in one


def test_reloads_and_reimports_leave_one_listener_set(child_report):
    assert child_report["listeners"] == [1, 1, 1]


def test_reloads_and_reimports_leave_one_gc_callback(child_report):
    """... whose counters the copy that is asked reads, whichever copy
    installed it."""
    assert child_report["gc_callbacks"] == 1
    assert child_report["gc_counted_by_the_new_copy"] == 1


def test_obs_imports_in_a_process_without_jax():
    """``paddle_tpu.obs`` stays pure stdlib: importable (its package
    stubbed, so ``paddle_tpu/__init__`` does not run) where importing jax
    fails, the bridge then a no-op and the span layer memory-only."""
    code = r"""
import sys, types
class NoJax:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("no jax in this process")
sys.meta_path.insert(0, NoJax())
pkg = types.ModuleType("paddle_tpu"); pkg.__path__ = [sys.argv[1] + "/paddle_tpu"]
sys.modules["paddle_tpu"] = pkg
from paddle_tpu import obs
assert obs.ledger.bridge_jax_monitoring() is False
with obs.tracing.span("t.plain"):
    pass
obs.LEDGER.record("k", duration_s=0.1)
assert obs.tracing.finished(name="t.plain") and "jax" not in sys.modules
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code, REPO],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# ------------------------------------------------ the five setup_* readers
MAIN = threading.main_thread().ident
OTHER = MAIN + 1
READERS = ("setup_trace_s", "setup_init_s", "setup_uncached_s",
           "setup_step_compiles", "setup_unattributed_s")
_ids = iter(range(1, 10 ** 6))


def _sp(name, t0, t1, parent=None, thread=MAIN, **attrs):
    return {"name": name, "t0": float(t0), "t1": float(t1),
            "duration_s": float(t1 - t0), "span_id": next(_ids),
            "parent_id": parent and parent["span_id"], "trace_id": None,
            "thread": thread, "attrs": attrs}


def _startup_spans():
    """Process start 100, window 160..170. Main thread: first span at 110;
    nn.init 110-114 with a 1 s uncached compile inside; the loader 114-115;
    init_state 115-120; two step calls that each trace, lower and read the
    step's program from the cache; a check with an uncached compile outside
    every region; a reader thread's compile; spans in and after the
    window."""
    init = _sp("nn.init", 110, 114, initializer="Normal", bytes=4096)
    spans = [
        _sp("compile.trace", 110.0, 110.5, init, fun="_normal", self_s=0.5),
        _sp("compile.lower", 110.5, 111.0, init, fun="jit(_normal)"),
        _sp("compile.backend", 111, 112, init, fun="jit(_normal)",
            cache="uncached"),
        init,
        _sp("io.loader.start", 114, 115, workers=2, buffered=True),
    ]
    state = _sp("train.init_state", 115, 120, leaves=6, bytes=1 << 20)
    spans += [_sp("train.init_state.params", 115, 117, state, leaves=2,
                  bytes=1 << 18),
              _sp("train.init_state.opt_state", 117, 120, state, leaves=4,
                  bytes=3 << 18), state]
    for t in (120, 135):
        step = _sp("train.step", t, t + 12)
        call = _sp("train.step.call", t + 1, t + 11, step)
        nested = _sp("compile.trace", t + 2, t + 3, call, fun="inner",
                     self_s=1.0)
        spans += [
            nested,
            _sp("compile.trace", t + 1, t + 6, call, fun="train_step",
                self_s=4.0),
            _sp("compile.lower", t + 6, t + 8, call, fun="jit(train_step)"),
            _sp("compile.backend", t + 8, t + 10, call,
                fun="jit(train_step)", cache="hit", read_s=1.5),
            call, step]
    spans += [
        # the reference check: a compile that is nobody's child
        _sp("compile.lower", 150, 151, fun="jit(check)"),
        _sp("compile.backend", 151, 154, fun="jit(check)", cache="written"),
        # another thread: counts for the sums over all threads only
        _sp("compile.trace", 121, 122, thread=OTHER, fun="convert",
            self_s=1.0),
        _sp("compile.lower", 122, 122.5, thread=OTHER, fun="jit(convert)"),
        _sp("compile.backend", 122.5, 123, thread=OTHER, fun="jit(convert)",
            cache="uncached"),
        _sp("io.next_batch", 130, 158, thread=OTHER),
        # inside and after the window: never start-up
        _sp("train.step", 161, 162),
        _sp("compile.backend", 165, 166, fun="jit(late)", cache="uncached"),
        _sp("nn.init", 171, 172, initializer="Normal", bytes=1),
    ]
    return spans


def _record():
    return {"window": {"start": 160.0, "end": 170.0, "seconds": 10.0},
            "setup_s": 60.0}


def _read_all(monkeypatch, spans, record, full=False):
    monkeypatch.setattr(tracing, "finished", lambda **kw: list(spans))
    monkeypatch.setattr(tracing, "ring_full", lambda: full)
    return {name: cells.load_module("layer_metrics", name).read(record)
            for name in READERS}


def test_readers_on_a_synthetic_start(monkeypatch, capsys):
    got = _read_all(monkeypatch, _startup_spans(), _record())
    notes = {k: v for line in capsys.readouterr().out.splitlines()
             for k, v in json.loads(line).items()}
    # self_s of every trace span before the window, all threads
    assert got["setup_trace_s"] == pytest.approx(0.5 + 2 * (1 + 4) + 1.0)
    # nn.init 4 s less the 2 s of trace + lower + backend inside; all of
    # init_state (its children are not counted again)
    assert got["setup_init_s"] == pytest.approx(2.0 + 5.0)
    # uncached programs: _normal (0.5 + 1) and the other thread's (0.5 + 0.5)
    assert got["setup_uncached_s"] == pytest.approx(2.5)
    assert got["setup_step_compiles"] == 2
    # main thread covered: 110-132, 135-147 and 150-154 of 100-160
    assert got["setup_unattributed_s"] == pytest.approx(60 - 22 - 12 - 4)
    account = notes["setup_account_s"]
    assert account["sum"] == pytest.approx(60.0)
    assert account == pytest.approx({
        "trace": 0.5 + 2 * 5, "compile": 1.5 + 2 * 4 + 4, "init": 7.0,
        "other_spans": 1 + 2 * 3, "unattributed": 22.0, "sum": 60.0})
    assert notes["setup_gaps"][0] == {"s": 10.0, "at_s": 0.0,
                                      "after": "process start",
                                      "before": "nn.init"}
    assert notes["setup_gaps"][1]["before"] == "window"
    top = notes["startup_programs"][0]
    assert top["fun"] == "train_step" and top["programs"] == 2
    assert top["cache"] == {"hit": 2} and top["trace_s"] == 8.0
    assert notes["startup_uncached"]["programs"] == 2
    assert notes["startup_spans"]["nn.init"] == {"n": 1, "s": 4.0,
                                                 "bytes": 4096}
    assert notes["startup_spans"]["io.loader.start"]["n"] == 1


@pytest.mark.parametrize("case", ["ring_full", "parent_commit", "no_ring_api",
                                  "no_window"])
def test_readers_report_nothing_rather_than_a_short_sum(monkeypatch, case):
    spans = _startup_spans()
    if case == "parent_commit":  # a program without the bridge or the regions
        spans = [s for s in spans if not s["name"].startswith((
            "compile.", "nn.init", "train.init_state", "io.loader"))]
    if case == "no_ring_api":  # ... or with a span layer older than this PR
        monkeypatch.delattr(tracing, "ring_full")
        monkeypatch.setattr(tracing, "finished", lambda **kw: list(spans))
        got = {n: cells.load_module("layer_metrics", n).read(_record())
               for n in READERS}
    else:
        got = _read_all(monkeypatch, spans,
                        {} if case == "no_window" else _record(),
                        full=(case == "ring_full"))
    assert got == dict.fromkeys(READERS)


@pytest.mark.parametrize("config, traffic, step_compiles", [
    ("bert-base", "mlm", 1),   # no buffers: the step's jit key is stable
    ("resnet50", "imagenet", 2),  # batch norm's buffers: traced twice
])
def test_readers_on_a_rehearsed_train_cell(tmp_path, config, traffic,
                                           step_compiles):
    """The train driver end to end at toy width on the CPU (the benchmark's
    rehearsal), then the five readers on its record: each reports a number,
    the classes add up to ``setup_s``, and the step's program is counted
    once, or twice where the model has buffers."""
    from benchmark.harness import rehearsal, runner
    from benchmark.tests import toy

    tracing.reset()  # the ring may be full of other tests' spans
    t_start = time.monotonic()
    try:
        result, _ = rehearsal.rehearse(
            config, getattr(toy, traffic)(),
            cells.load_module("configs", config).TOY, str(tmp_path),
            seconds=0.5)
    finally:
        runner.stop_children()
    record = result["record"]
    record["setup_s"] = record["window"]["start"] - t_start
    got = {name: cells.load_module("layer_metrics", name).read(record)
           for name in READERS}
    assert all(v is not None and np.isfinite(v) for v in got.values()), got
    assert got["setup_step_compiles"] == step_compiles
    assert 0 < got["setup_trace_s"] < record["setup_s"]
    assert 0 < got["setup_uncached_s"] <= record["setup_compile_s"] + 1e-6
    startup = cells.load_module("layer_metrics", "_startup")
    account = startup.account(startup.spans(record))
    assert account["sum"] == pytest.approx(record["setup_s"], abs=1e-6)
    assert account["init"] == got["setup_init_s"] > 0
    assert account["unattributed"] == got["setup_unattributed_s"] >= 0
    # the benchmark's own meter and the program's bridge hear the same events
    assert account["compile"] == pytest.approx(record["setup_compile_s"],
                                               rel=0.1)
    # nothing new on the hot path: the window holds the old span names, and
    # the collections that stopped it
    window = {s["name"] for s in tracing.finished()
              if record["window"]["start"] <= s["t0"]
              <= record["window"]["end"]}
    assert window <= {"host.gc", "io.next_batch", "io.next_batch.wait",
                      "io.next_batch.convert", "io.worker.produce",
                      "spmd.shard_batch", "train.step", "train.step.lr",
                      "train.step.buffers_in", "train.step.call",
                      "train.step.buffers_out"}, window


def test_readers_are_declared_as_the_benchmark_lists_them():
    bench = cells.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m, mod = listed[name], cells.load_module("layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert "workloads" not in m and m["moves"] == "setup_s"
        assert m["layer"] == listed["setup_compile_s"]["layer"]
    # appended together and in this order (later PRs append after them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(READERS[0])
    assert names[first:first + len(READERS)] == list(READERS)


def test_chip_smoke_reads_the_bridge_and_has_no_listener_of_its_own():
    import chip_smoke

    source = open(chip_smoke.__file__).read()
    assert "jax.monitoring" not in source.replace("``jax.monitoring``", "")
    seconds, mark = chip_smoke._compile_seconds(), time.monotonic()

    def smoked(x):
        return x - 4

    jax.jit(smoked)(jnp.ones(2))
    after = chip_smoke._compile_seconds()
    assert after > seconds
    hits, writes = chip_smoke._cache_events(mark)
    assert (hits, writes) in ((0, 0), (0, 1), (1, 0))
