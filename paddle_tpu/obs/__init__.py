"""Unified telemetry: metrics registry, span tracing, goodput
accounting, compile ledger, Prometheus exposition.

One instrumentation layer for training, serving, and CI (ROADMAP items
3 and 4). Pure-stdlib on purpose: importable from the resilience
runtime, the serving engine and clients of the wire protocol without
dragging jax into anything that doesn't already have it.

Quick tour::

    from paddle_tpu import obs

    reqs = obs.counter("myapp_requests_total", "requests served")
    reqs.inc()
    lat = obs.histogram("myapp_latency_seconds", "request latency")
    lat.observe(0.012)
    print(obs.render())               # Prometheus text exposition

    with obs.tracing.span("myapp.handler", trace_id=obs.new_trace_id()):
        ...                           # lands in the shared span table

    obs.goodput.account("checkpoint", 2.5)
    obs.goodput.report()              # {"goodput": ..., "step_s": ...}

    obs.LEDGER.record("mykernel", duration_s=dt, compiled=compiled)
"""
from . import goodput, ledger, metrics, prometheus, tracing  # noqa: F401
from .ledger import LEDGER, CompileLedger  # noqa: F401
from .metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                      Registry, counter, gauge, histogram, log_buckets)
from .prometheus import render  # noqa: F401
from .tracing import new_trace_id, span, start_span  # noqa: F401

__all__ = [
    "metrics", "prometheus", "tracing", "goodput", "ledger",
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram", "log_buckets", "render",
    "LEDGER", "CompileLedger", "new_trace_id", "span", "start_span",
]
