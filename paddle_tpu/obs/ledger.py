"""Compile ledger: a record of every XLA compile the process pays.

On TPU, unexpected recompiles are the dominant silent performance
regression (tracelint's TPU101–TPU104 catch them statically; the ledger
catches them at runtime), and compiled-program *structure* — op mix,
cost-analysis FLOPs/bytes — is a chip-independent count that repeats
exactly from run to run. Every entry records:

    key          caller-chosen identity (e.g. "serving/bucket8")
    kind         "aot" (jax AOT .lower().compile()), "callable", ...
    duration_s   wall-clock compile time
    flops / bytes_accessed   from ``compiled.cost_analysis()``
    op_counts    {hlo_opcode: n} parsed from ``compiled.as_text()``
    fingerprint  sha256 over the ordered opcode sequence — a
                 *structural* HLO identity that ignores value names and
                 literal payloads, so two compiles of the same program
                 shape match even when buffer ids differ

The serving suites read it to count real compiles apart from store
loads (tests/test_artifact_serving.py, test_quant_serving.py,
test_sharded_serving.py); the counts are exact, never a speed.

Those entries are the compiles a caller reports. What jax itself traces,
lowers and compiles — every ``jax.jit`` program of the process — reaches
the span layer and this module's two metrics through
:func:`bridge_jax_monitoring`, the process's one set of listeners on
``jax.monitoring`` (installed at ``paddle_tpu``'s import). The interpreter's
own pauses reach both the same way: :func:`bridge_gc`, the process's one
callback on ``gc.callbacks``.
"""
import gc
import hashlib
import re
import sys
import threading
import time

from . import metrics as _metrics
from . import tracing as _tracing

_COMPILES = _metrics.counter(
    "paddle_compile_events_total",
    "XLA compile events recorded in the compile ledger",
    labelnames=("kind",))
_COMPILE_SECONDS = _metrics.histogram(
    "paddle_compile_seconds",
    "Duration of recorded compile events",
    buckets=_metrics.log_buckets(0.001, 4.0, 10))

_OPCODE_RE = re.compile(r"^[a-zA-Z][\w-]*")


def _strip_hlo_type(rhs):
    """Drop the leading result type from an HLO instruction RHS —
    either a whitespace-free shape like ``f32[8,4]{1,0}`` or a
    parenthesized tuple type like ``(f32[2]{0}, s32[])`` (which
    contains spaces, so token-splitting alone would mis-parse)."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[i + 1:].lstrip()
        return ""
    parts = rhs.split(None, 1)
    return parts[1] if len(parts) > 1 else ""


def hlo_typed_opcodes(hlo_text):
    """Ordered ``opcode:result_dtype`` sequence of every instruction in
    an HLO module text dump (computation headers and metadata lines are
    skipped) — ``convert:f32``, ``parameter:s8``, ``dot:f32``;
    tuple-typed results report ``tuple``. The ONE parsing pass: the
    untyped view is a projection (:func:`hlo_opcodes`). The dtype
    dimension is what tests/test_quant_serving.py reads: a
    ``parameter:s8`` / ``parameter:bf16`` count proves
    reduced-precision weights actually reached XLA instead of silently
    promoting to f32 upstream of the lowering."""
    ops = []
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        rhs_full = line.split(" = ", 1)[1].lstrip()
        if rhs_full.startswith("("):
            dtype = "tuple"
        else:
            head = rhs_full.split(None, 1)[0]
            dtype = head.split("[", 1)[0]
        rhs = _strip_hlo_type(rhs_full)
        m = _OPCODE_RE.match(rhs)
        if m and "(" in rhs[m.end():m.end() + 1]:
            ops.append(f"{m.group(0)}:{dtype}")
    return ops


def hlo_opcodes(hlo_text):
    """Ordered opcode sequence of every instruction in an HLO module
    text dump — the dtype-less projection of
    :func:`hlo_typed_opcodes` (opcode names never contain ``:``), so
    there is exactly one parser to maintain."""
    return [op.partition(":")[0] for op in hlo_typed_opcodes(hlo_text)]


def hlo_fingerprint(opcodes):
    """Structural identity: sha256 over the ordered opcode sequence."""
    h = hashlib.sha256()
    for op in opcodes:
        h.update(op.encode("ascii", "replace"))
        h.update(b"\n")
    return h.hexdigest()[:16]


def analyze_compiled(compiled):
    """Best-effort structural + cost analysis of a jax AOT ``Compiled``.

    Never raises: backends without as_text()/cost_analysis() yield a
    partial record (the ledger must not break serving when XLA's
    introspection surface shifts under a jax upgrade)."""
    out = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            flops = cost.get("flops")
            if flops is not None:
                out["flops"] = float(flops)
            acc = cost.get("bytes accessed")
            if acc is not None:
                out["bytes_accessed"] = float(acc)
    except Exception:  # noqa: BLE001 — introspection is best-effort
        pass
    try:
        # ONE parse of the HLO text; the untyped view (op_counts,
        # n_ops, the structural fingerprint — all byte-compatible with
        # pre-quant baselines) is a projection of the typed sequence
        typed_ops = hlo_typed_opcodes(compiled.as_text())
        ops = [op.partition(":")[0] for op in typed_ops]
        counts = {}
        for op in ops:
            counts[op] = counts.get(op, 0) + 1
        out["op_counts"] = counts
        out["n_ops"] = len(ops)
        out["fingerprint"] = hlo_fingerprint(ops)
        typed = {}
        for op in typed_ops:
            typed[op] = typed.get(op, 0) + 1
        # opcode:result_dtype counts — the reduced-precision evidence
        # (parameter:s8 / parameter:bf16 / convert:f32) that
        # tests/test_quant_serving.py asserts on
        out["typed_op_counts"] = typed
    except Exception:  # noqa: BLE001
        pass
    return out


class CompileLedger:
    """Append-only, bounded record of compile events."""

    def __init__(self, cap=1024):
        self._lock = threading.Lock()
        self._events = []
        self._cap = cap

    def record(self, key, duration_s=None, compiled=None, kind="aot",
               extra=None):
        """Record one compile event; returns the event dict."""
        ev = {"key": str(key), "kind": kind, "ts": time.time()}
        if duration_s is not None:
            ev["duration_s"] = round(float(duration_s), 6)
        if compiled is not None:
            ev.update(analyze_compiled(compiled))
        if extra:
            ev.update(extra)
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._cap:
                del self._events[:len(self._events) - self._cap]
        _COMPILES.inc(kind=kind)
        if duration_s is not None:
            _COMPILE_SECONDS.observe(float(duration_s))
            _tracing.observe(f"compile:{key}", float(duration_s))
        return ev

    def events(self, key_prefix=None):
        with self._lock:
            evs = list(self._events)
        if key_prefix is not None:
            evs = [e for e in evs if e["key"].startswith(key_prefix)]
        return evs

    def totals(self, key_prefix=None):
        """Aggregate view: compile count, summed flops/bytes, merged
        op counts."""
        evs = self.events(key_prefix)
        op_counts = {}
        flops = 0.0
        acc = 0.0
        for e in evs:
            flops += e.get("flops", 0.0)
            acc += e.get("bytes_accessed", 0.0)
            for op, n in e.get("op_counts", {}).items():
                op_counts[op] = op_counts.get(op, 0) + n
        return {"compiles": len(evs), "flops": flops,
                "bytes_accessed": acc, "op_counts": op_counts,
                "n_ops": sum(op_counts.values())}

    def reset(self):
        with self._lock:
            self._events = []


#: Default process ledger (the serving engine's AOT compiles land here).
LEDGER = CompileLedger()


# ------------------------------------------ jax's compile events -> spans
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BRIDGED = {  # jax's duration event -> (span name, the metrics' ``kind``)
    _TRACE_EVENT: ("compile.trace", "trace"),
    _LOWER_EVENT: ("compile.lower", "lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend", "backend"),
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"
_CACHE_READ_S = "/jax/compilation_cache/cache_retrieval_time_sec"
#: the attribute on ``jax.monitoring`` that says the listeners are in: it
#: outlives a reload or a second import of this module, as they do
_INSTALLED = "_paddle_tpu_compile_bridge"
#: a trace region NESTED in another trace region or in a lowering becomes a
#: span of its own from this many seconds on. Every jitted ``jax.numpy``
#: function a traced program calls is such a region (BERT's train step:
#: 6,900 of them, 0.1 ms each), and so is every helper a lowering rule traces
#: (760 ``add`` / ``bitwise_xor`` under the BERT cell's parameter draws, my
#: chip run, PR 35): the short ones stay in the enclosing span and out of the
#: ring
_NESTED_TRACE_MIN_S = 0.01
_bridge_tls = threading.local()


def _on_region_start(event, _value, **_):
    """jax announces a timed region's start as a scalar. Trace regions
    nest in trace regions (a jit traced inside a jit) and in lowering
    regions (a lowering rule that traces a helper): open a frame, which for
    a trace region sums the seconds of the ``compile.trace`` spans nested
    in it, so that the outer span can say what was its own."""
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        frames = getattr(_bridge_tls, "frames", None)
        if frames is None:
            frames = _bridge_tls.frames = []
        frames.append(0.0)


def _on_cache_event(event, **_):
    """jax says what its persistent cache did before it ends the backend
    region on the same thread: a hit when it read the executable, a
    "miss" when it WROTE the one it just compiled."""
    if event == _CACHE_HIT:
        _bridge_tls.cache = "hit"
    elif event == _CACHE_WRITE:
        _bridge_tls.cache = "written"


def _on_duration(event, duration, fun_name=None, **_):
    """One of jax's regions ended NOW on this thread, ``duration`` seconds
    long: a pre-measured span under the thread's ambient span, and a
    count and an observation on the ledger's metrics."""
    if event == _CACHE_READ_S:
        _bridge_tls.read_s = duration
        return
    bridged = _BRIDGED.get(event)
    if bridged is None:
        return
    name, kind = bridged
    attrs = {"fun": fun_name}
    if kind != "backend":
        frames = getattr(_bridge_tls, "frames", None)
        nested = frames.pop() if frames else 0.0
    if kind == "trace":
        if frames:  # nested in another trace region, or in a lowering
            if duration < _NESTED_TRACE_MIN_S:
                return
            frames[-1] += duration
        attrs["self_s"] = max(duration - nested, 0.0)
    elif kind == "backend":
        # neither event since the last backend region: the program was
        # compiled and not kept (under the cache's threshold, or no cache)
        attrs["cache"] = _bridge_tls.__dict__.pop("cache", "uncached")
        read_s = _bridge_tls.__dict__.pop("read_s", None)
        if read_s is not None:
            attrs["read_s"] = read_s
    _tracing.record_span(name, duration, **attrs)
    _COMPILES.inc(kind=kind)
    _COMPILE_SECONDS.observe(duration)


def bridge_jax_monitoring():
    """Install the process's one set of ``jax.monitoring`` listeners: each
    trace / lowering / backend-compile region jax times becomes a span
    ``compile.trace`` [``fun``, ``self_s``: the duration less the
    ``compile.trace`` spans nested in it; an outermost region always, one
    nested in a trace or lowering region from ``_NESTED_TRACE_MIN_S`` on, so
    the ``self_s`` of a stretch add up to its tracing seconds] /
    ``compile.lower`` [``fun``] /
    ``compile.backend`` [``fun``, ``cache`` = ``hit`` | ``written`` |
    ``uncached``, ``read_s`` on a hit], a child of whatever span the
    compiling thread is inside, and feeds ``paddle_compile_events_total
    {kind="trace"|"lower"|"backend"}`` and ``paddle_compile_seconds``.

    The spans are pre-measured (``tracing.record_span``): jax fires an
    event when its region ends, so ``t1`` is the callback's
    ``time.monotonic()`` and ``t0 = t1 - duration``. They are memory-only:
    a profiler's trace cannot take an event after the fact; it holds the
    region span the compile ran inside (``train.step.call`` as wide as its
    compile). There is no switch. Idempotent, whichever copy of this
    module is asked; False in a process that has not imported jax."""
    if "jax" not in sys.modules:
        return False
    import jax.monitoring as monitoring

    if not getattr(monitoring, _INSTALLED, False):
        setattr(monitoring, _INSTALLED, True)
        monitoring.register_scalar_listener(_on_region_start)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    return True


# ------------------------------------- the interpreter's collections -> spans
#: a collection of generation 0 or 1 is a span from this many seconds on (a
#: full collection always is): a large start runs some 10^5 young ones of
#: tens of microseconds, and the ring holds 8,192 spans
_GC_SPAN_MIN_S = 0.001
_GC_MARK = "_paddle_tpu_gc_totals"


def _gc_callback():
    """A callback for ``gc.callbacks`` with counters of its own. The
    interpreter calls it on the collecting thread before and after every
    collection, one collection at a time, every other Python thread stopped
    in between. It may interrupt its thread wherever an object is allocated,
    inside a locked region of the span layer or of the metrics registry
    too, so it takes no lock: ``tracing._record`` never waits for one, and
    the counters are plain lists that only it writes. All it needs is in
    its closure, so a young collection still counts while the interpreter
    takes the modules apart at exit."""
    counts, seconds = [0, 0, 0], [0.0, 0.0, 0.0]
    running = [None, None]  # the collection under way: its start, its region
    clock, min_s = time.monotonic, _GC_SPAN_MIN_S
    Span, record_span = _tracing.Span, _tracing.record_span

    def on_gc(phase, info):
        if phase == "start":
            if info["generation"] == 2:
                running[1] = Span("host.gc", attrs={"generation": 2})
            running[0] = clock()
            return
        t1, t0 = clock(), running[0]
        if t0 is None:  # installed while this collection ran
            return
        running[0] = None
        generation = info["generation"]
        counts[generation] += 1
        seconds[generation] += t1 - t0
        if generation == 2 or t1 - t0 >= min_s:
            attrs = {"collected": info["collected"],
                     "uncollectable": info["uncollectable"],
                     "counts": list(counts), "seconds": list(seconds)}
            region, running[1] = running[1], None
            if region is not None:
                region.finish(**attrs)
            else:
                record_span("host.gc", t1 - t0, generation=generation,
                            **attrs)

    setattr(on_gc, _GC_MARK,
            lambda: {g: (counts[g], seconds[g]) for g in range(3)})
    return on_gc


def gc_totals():
    """{generation: (collections, seconds paused)} since the bridge went
    in: what the two counters hold. None in a process without the bridge."""
    for f in gc.callbacks:
        totals = getattr(f, _GC_MARK, None)
        if totals is not None:
            return totals()
    return None


def _gc_families():
    """The registry's view of the callback's counters (a collector, not two
    ``Counter``s: a ``Counter``'s lock is one the interrupted thread may
    hold)."""
    rows = sorted((gc_totals() or {}).items())
    return [
        _metrics.Family(
            "paddle_gc_collections_total", "counter",
            "Collections of the cyclic garbage collector, by generation",
            [("", {"generation": str(g)}, n) for g, (n, _) in rows]),
        _metrics.Family(
            "paddle_gc_pause_seconds_total", "counter",
            "Seconds every Python thread stood still for a collection",
            [("", {"generation": str(g)}, s) for g, (_, s) in rows])]


def bridge_gc():
    """Install the process's one callback on ``gc.callbacks``: a collection
    of generation 2, or any that lasted ``_GC_SPAN_MIN_S`` or more, becomes
    a span ``host.gc`` [``generation``, ``collected``, ``uncollectable``,
    and ``counts`` / ``seconds``: the two counters by generation as they
    stood when it ended, which places the young collections in time] on
    the thread that triggered it, a child of the span that thread is
    inside and nobody's ambient parent. A full collection is a region
    span, opened at its start, so a profiler's trace holds it as
    ``paddle_tpu:host.gc``; a young one that turns out long is
    pre-measured, memory only. Every collection counts in
    ``paddle_gc_collections_total{generation}`` and
    ``paddle_gc_pause_seconds_total{generation}``; the young and short ones
    (two clock reads and two adds each) count there only.

    There is no switch. Idempotent, whichever copy of this module is asked
    (the callback in ``gc.callbacks`` carries its counters: ``gc_totals``
    reads the installed one's); a forked worker inherits it and counts its
    own collections in its own memory."""
    if gc_totals() is None:
        gc.callbacks.append(_gc_callback())
        _metrics.REGISTRY.register_collector(_gc_families)
    return True
