"""Profiler (reference: paddle/fluid/platform/profiler.* RecordEvent +
DeviceTracer/CUPTI; python fluid/profiler.py).

TPU-native: jax.profiler writes an XPlane trace (``.xplane.pb``, read with
``jax.profiler.ProfileData`` or TensorBoard); CUPTI's role is played by the
TPU runtime itself. The program's host spans are ``paddle_tpu.obs.tracing``
region spans: each is a ``jax.profiler.TraceAnnotation`` named
``paddle_tpu:<name>``, so a trace started here holds them on the device
operations' clock, and the step program's operations carry their module
and phase (``jax.named_scope``) in ``op_name``. ``summary()`` prints the
span layer's one table: RecordEvent, train-path, serving, checkpoint and
compile spans.
"""
import contextlib

import jax

from ..obs import tracing as _tracing

#: RAII span (reference: profiler.h:127): an ``obs.tracing`` region span.
#: Inside a traced request it inherits the ambient trace id.
RecordEvent = _tracing.span


def _start_trace(profile_path):
    """The profiler with the host tracer on TraceMe spans only: per-call
    Python events (a million frames a second) would bury the
    ``paddle_tpu:`` spans and slow the host they time."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(profile_path, profiler_options=opts)


def reset_summary():
    _tracing.reset_summary()


def summary(sorted_by="total", printer=print):
    """Aggregated span table (reference: profiler.cc PrintProfiler /
    'sorted by total time'). Includes every span the process recorded —
    RecordEvent, serving, checkpoint, compile — since the last
    ``reset_summary()``. Returns the rows; also prints a table."""
    rows = _tracing.summary_rows()
    key = {"total": "total", "calls": "calls", "avg": "avg",
           "max": "max", "min": "min"}.get(sorted_by, "total")
    rows.sort(key=lambda r: r[key], reverse=True)
    if printer is not None and rows:
        w = max(len(r["name"]) for r in rows)
        printer(f"{'Event':<{w}}  {'Calls':>7} {'Total(s)':>10} "
                f"{'Avg(s)':>10} {'Max(s)':>10} {'Min(s)':>10}")
        for r in rows:
            printer(f"{r['name']:<{w}}  {r['calls']:>7} "
                    f"{r['total']:>10.6f} {r['avg']:>10.6f} "
                    f"{r['max']:>10.6f} {r['min']:>10.6f}")
    return rows


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    """paddle.utils.profiler.profiler context (fluid/profiler.py analog)."""
    _start_trace(profile_path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_profiler(state="All", tracer_option="Default",
                   profile_path="/tmp/profile"):
    _start_trace(profile_path)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    jax.profiler.stop_trace()


def cuda_profiler(*args, **kwargs):
    raise NotImplementedError("use jax.profiler traces on TPU")


# --------------------------------------------- legacy fluid-profiler API
# (reference: python/paddle/utils/profiler.py ProfilerOptions/Profiler/
# get_profiler wrapping fluid.profiler start/stop)


class ProfilerOptions:
    def __init__(self, options=None):
        self.options = {
            "state": "All", "sorted_key": "default",
            "tracer_level": "Default", "batch_range": [0, 100],
            "output_thread_detail": False, "profile_path": "none",
            "timeline_path": "none", "op_summary_path": "none",
        }
        if options is not None:
            self.options.update(options)

    def with_state(self, state):
        new = ProfilerOptions(dict(self.options))
        new.options["state"] = state
        return new

    def __getitem__(self, name):
        return self.options[name]


class Profiler:
    """Context-manager profiler (reference: utils/profiler.py Profiler):
    start/stop the jax trace + host span aggregation."""

    def __init__(self, enabled=True, options=None):
        self.enabled = enabled
        self.profiler_options = options or ProfilerOptions()
        self._span = None

    def __enter__(self):
        if self.enabled:
            reset_summary()
            self._span = RecordEvent("Profiler")
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.__exit__(*exc)
            self._span = None
        return False

    def reset(self):
        reset_summary()


_profiler = None


def get_profiler(options=None):
    global _profiler
    if _profiler is None:
        _profiler = Profiler(options=options)
    return _profiler
