"""Start and stop the profiler for a steady slice, and read it back."""
import os
import shutil

from . import xplane


def start(trace_dir):
    import jax.profiler

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call python events: they bloat
    opts.host_tracer_level = 2     # the trace and slow the host; TraceMe
    jax.profiler.start_trace(trace_dir, profiler_options=opts)  # spans stay


def stop_and_load(trace_dir):
    """Stop the profiler and return the trace in ``xplane``'s plain form
    (None where the profiler wrote nothing)."""
    import jax.profiler

    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    return xplane.load(path) if path else None
