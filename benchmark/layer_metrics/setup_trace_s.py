"""Seconds before the window that jax spent TRACING Python into jaxprs: the
sum of ``self_s`` over the program's ``compile.trace`` spans that end before
the window (``paddle_tpu.obs.ledger`` bridges jax's
``jaxpr_trace_duration`` events into the span layer; ``self_s`` is a
region's duration less the trace spans nested in it, so nothing counts
twice). No cache helps it: the same warm and cold. ``setup_compile_s`` leaves
it out by design. The ten largest programs by ``fun`` (trace / lower /
backend seconds, what the cache did) go out as a note line."""
import json

from benchmark.harness import cells

_startup = cells.load_module("layer_metrics", "_startup")

LAYER = _startup.LAYER
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(record):
    startup = _startup.spans(record)
    if startup is None:
        return None
    before = startup["before"]
    print(json.dumps({"startup_programs": programs(before)}), flush=True)
    return sum(s["attrs"].get("self_s", 0.0) for s in before
               if s["name"] == _startup.TRACE)


def programs(found, n=10):
    """The ``n`` programs that cost most seconds: {fun, programs (backend
    regions), trace_s, lower_s, backend_s, cache: {hit|written|uncached:
    count}}."""
    rows = {}
    for s in found:
        if not s["name"].startswith("compile."):
            continue
        row = rows.setdefault(_startup.program(s["attrs"].get("fun")), {
            "programs": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache": {}})
        if s["name"] == _startup.TRACE:
            row["trace_s"] += s["attrs"].get("self_s", 0.0)
        elif s["name"] == _startup.LOWER:
            row["lower_s"] += s["t1"] - s["t0"]
        else:
            row["programs"] += 1
            row["backend_s"] += s["t1"] - s["t0"]
            said = s["attrs"].get("cache", "?")
            row["cache"][said] = row["cache"].get(said, 0) + 1
    top = sorted(rows.items(), reverse=True, key=lambda kv: (
        kv[1]["trace_s"] + kv[1]["lower_s"] + kv[1]["backend_s"]))[:n]
    return [dict({k: round(v, 3) if isinstance(v, float) else v
                  for k, v in row.items()}, fun=fun) for fun, row in top]
