"""The benchmark's own spans around its calls into each layer: kept in
memory on the host clock (``time.monotonic``), and in a traced run
also written into the profiler's trace (``bench:<name>``) so idle gaps of
the device can be labelled by what the benchmark's loop was doing."""
import contextlib
import time


class SpanRecorder:
    def __init__(self, annotate=False):
        self.spans = []  # (name, t0, t1) on time.monotonic
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.annotate:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.monotonic()))
            if ann is not None:
                ann.__exit__(None, None, None)
