"""Span tracing: one clock, one summary table, one bounded ring, and the
profiler's trace.

A *span* is a named timed region: ``name``, ``t0``/``t1`` (seconds on
``time.monotonic``, the clock ``obs/goodput.py`` and the benchmark use),
``span_id``, ``parent_id``, ``trace_id``, ``thread`` and ``attrs``. Every
finished span lands in the aggregation table (``summary_rows``) and in a
bounded ring of finished spans (``finished``).

A *region* span (``span()``, or ``start_span`` ... ``finish``) is also a
``jax.profiler.TraceAnnotation`` named ``paddle_tpu:<name>``, entered and
left with the span, so a profiler session holds the program's host spans
on the same clock as the device's operations. With no session running the
annotation is TraceMe's inactive path; that is all "tracing off" means
here: there is no switch. jax is only used where the process has already
imported it, so the jax-free wire clients stay jax-free and memory-only.

Spans come from: start-up (``nn.init`` a parameter drawn,
``train.build_step``, ``train.init_state`` + ``.params`` / ``.opt_state``,
``io.loader.start``, and jax's own trace / lowering / backend-compile
regions as pre-measured ``compile.trace`` / ``.lower`` / ``.backend``,
bridged from ``jax.monitoring`` by ``obs/ledger.py``: memory only, as every
``record_span``; the interpreter's collections as ``host.gc``, bridged
from ``gc.callbacks`` there too), the train path (``io.next_batch``,
``spmd.shard_batch``, ``train.step`` and their children), the serving engine
(``serving.scheduler.loop`` / ``.execute`` / ``.compile`` as regions;
``serving.queue`` / ``.request`` / ``.reply`` pre-measured per traced
request with ``record_span``), checkpoints, and the legacy
``utils.profiler.RecordEvent`` (an alias of ``span``).

``span()`` installs itself as the thread's ambient parent: spans opened
inside it on the same thread take its id as ``parent_id``; an explicit
``parent_id=`` wins (the engine scheduler finishes what a handler thread
opened). Trace ids are 64-bit, non-zero, hex-rendered; ``trace(tid)``
installs an ambient id for the thread and an explicit ``trace_id=`` wins.
"""
import collections
import contextlib
import functools
import itertools
import random
import sys
import threading
import time

#: prefix of the program's spans in the profiler's trace
ANNOTATION_PREFIX = "paddle_tpu:"
#: finished spans kept; a benchmark run (start-up, a 10 s window, a traced
#: slice) leaves under two thousand. A reader that sums over a stretch of
#: the run and finds the ring full cannot know what fell out: it reports
#: nothing rather than a short sum
_RING = 8192

_lock = threading.Lock()
_finished = collections.deque(maxlen=_RING)
#: finished spans on their way into the ring, oldest first. ``_record`` puts
#: a span here without a lock and moves what is here into the ring only if it
#: gets ``_lock`` without waiting: a span may finish on a thread that already
#: holds ``_lock`` further down its own stack (an allocation under the lock
#: starts a collection, whose callback finishes ``host.gc``), or in a forked
#: child whose copy of the lock another thread of the parent held. Whoever
#: holds the lock takes what the others left, and every reader drains it
#: first, so nothing is lost and nothing is seen late (unbounded for that
#: reason: it is empty again as soon as anyone gets the lock)
_pending = collections.deque()
_agg = {}  # name -> [calls, total_s, max_s, min_s]
_tls = threading.local()
_span_ids = itertools.count(1)  # next() is atomic under the GIL


def new_trace_id():
    """Random non-zero u64 (0 means "no trace" on the wire)."""
    tid = 0
    while tid == 0:
        tid = random.getrandbits(64)
    return tid


def format_trace_id(tid):
    return f"{tid:016x}"


def current_trace_id():
    """The ambient trace id installed by :func:`trace` (None outside)."""
    return getattr(_tls, "trace_id", None)


@contextlib.contextmanager
def trace(trace_id):
    """Install ``trace_id`` as the current thread's ambient id."""
    prev = getattr(_tls, "trace_id", None)
    _tls.trace_id = trace_id
    try:
        yield trace_id
    finally:
        _tls.trace_id = prev


def _annotation(name):
    """An entered ``TraceAnnotation`` for a region span, or None in a
    process that has not imported jax."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    ann.__enter__()
    return ann


class Span:
    """One timed region, open until :meth:`finish`. Used as a context
    manager (:func:`span`) it is also the thread's ambient parent. A Span
    may be finished from another thread than it was started on: the engine
    scheduler finishes queue spans a handler thread opened."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "t0", "t1", "thread", "_ann")

    def __init__(self, name, trace_id=None, parent_id=None, attrs=None,
                 annotate=True):
        self.name = name
        self.trace_id = (trace_id if trace_id is not None
                         else getattr(_tls, "trace_id", None))
        self.span_id = next(_span_ids)
        if parent_id is None:
            stack = getattr(_tls, "stack", None)
            if stack:
                parent_id = stack[-1].span_id
        self.parent_id = parent_id
        self.attrs = attrs or {}
        self.thread = threading.get_ident()
        self.t1 = None
        self._ann = _annotation(name) if annotate else None
        self.t0 = time.monotonic()

    @property
    def duration_s(self):
        return None if self.t1 is None else self.t1 - self.t0

    def finish(self, **attrs):
        """Record the span (idempotent). Extra attrs merge in."""
        if self.t1 is not None:
            return self
        self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if attrs:
            self.attrs.update(attrs)
        _record(self)
        return self

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()
        self.finish()
        return False

    def as_dict(self):
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "duration_s": self.duration_s, "span_id": self.span_id,
                "parent_id": self.parent_id, "trace_id": self.trace_id,
                "thread": self.thread, "attrs": dict(self.attrs)}


def start_span(name, trace_id=None, parent_id=None, **attrs):
    """Open a span. Either the caller calls ``finish()`` (from any
    thread), or it is used as ``with span("io.next_batch") as sp:`` and is
    then the ambient parent of the spans opened inside it on this thread."""
    return Span(name, trace_id, parent_id, attrs)


span = start_span


def spanned(name, **attrs):
    """Decorator: each call of the function is one region span ``name``
    (the ambient parent of what the call opens on its thread)."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with Span(name, attrs=dict(attrs)):
                return fn(*args, **kwargs)
        return call
    return wrap


def _agg_update_locked(name, duration_s):
    """Fold one duration into the summary table. Caller holds _lock."""
    rec = _agg.get(name)
    if rec is None:
        rec = _agg[name] = [0, 0.0, 0.0, float("inf")]
    rec[0] += 1
    rec[1] += duration_s
    rec[2] = max(rec[2], duration_s)
    rec[3] = min(rec[3], duration_s)


def _drain_locked():
    """Move the pending spans into the ring and the summary table. Caller
    holds _lock; a span that arrives meanwhile (from another thread, or from
    a collection this loop's own allocations started) is taken too."""
    while _pending:
        sp = _pending.popleft()
        _finished.append(sp)
        _agg_update_locked(sp.name, sp.t1 - sp.t0)


def _record(sp):
    _pending.append(sp)
    if _lock.acquire(blocking=False):
        try:
            _drain_locked()
        finally:
            _lock.release()


def observe(name, duration_s):
    """Aggregate a pre-measured duration into the summary table only —
    no buffer entry, no Span object (the cheap path for untraced hot
    traffic)."""
    with _lock:
        _agg_update_locked(name, float(duration_s))


def record_span(name, duration_s, trace_id=None, parent_id=None, **attrs):
    """Record an already-measured region that ends now as a finished span
    (``t0 = t1 - duration_s``; memory only: the profiler's trace cannot
    take an event after the fact). The engine measures one batch and
    attributes it to every traced request in the group."""
    sp = Span(name, trace_id, parent_id, attrs, annotate=False)
    sp.t1 = time.monotonic()
    sp.t0 = sp.t1 - float(duration_s)
    _record(sp)
    return sp


def finished(trace_id=None, name=None):
    """Finished spans (as dicts, oldest first), optionally filtered by
    trace id and/or span name. The ring is bounded: a window to read
    after a run, not a durable trace store."""
    with _lock:
        _drain_locked()
        spans = list(_finished)
    return [s.as_dict() for s in spans
            if (trace_id is None or s.trace_id == trace_id)
            and (name is None or s.name == name)]


def ring_full():
    """True once the ring holds ``_RING`` spans: older ones may have fallen
    out, so a sum over "everything since X" may be short."""
    with _lock:
        _drain_locked()
        return len(_finished) == _RING


def self_times(spans):
    """``span_id -> seconds`` of each span's self time: its duration minus
    the union of the parts of it that its children cover (children on
    other threads may overlap each other; a union counts them once)."""
    children = collections.defaultdict(list)
    for s in spans:
        if s["parent_id"] is not None:
            children[s["parent_id"]].append(s)
    out = {}
    for s in spans:
        covered, end = 0.0, s["t0"]
        for c in sorted(children.get(s["span_id"], ()),
                        key=lambda c: c["t0"]):
            lo, hi = max(c["t0"], end), min(c["t1"], s["t1"])
            if hi > lo:
                covered += hi - lo
                end = hi
        out[s["span_id"]] = (s["t1"] - s["t0"]) - covered
    return out


def summary_rows():
    """Aggregated per-name rows, the profiler.summary() table schema:
    {name, calls, total, avg, max, min}."""
    with _lock:
        _drain_locked()
        return [{"name": n, "calls": c, "total": tot, "avg": tot / c,
                 "max": mx, "min": mn}
                for n, (c, tot, mx, mn) in _agg.items()]


def reset_summary():
    """Clear the aggregation table (the profiler.reset_summary()
    contract); the finished-span ring survives."""
    with _lock:
        _drain_locked()
        _agg.clear()


def reset():
    """Clear both the aggregation table and the finished-span ring."""
    with _lock:
        _pending.clear()
        _agg.clear()
        _finished.clear()
