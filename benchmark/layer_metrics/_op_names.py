"""What the expert-layer and kernel readers share: the traced slice's
operations with their raw HLO ``op_name`` (``program_trace.ops`` keeps only
the module scopes; ``moe.route`` and a kernel's name are neither), and the
test for one ``jax.named_scope`` among an ``op_name``'s path elements."""
from benchmark.harness import program_trace, xplane


def op_events(record):
    """[(event name, op_name, start_ns, end_ns)] of the operations on the
    first device that ran anything in the traced slice; [] without a
    readable trace."""
    if "op_events" not in record:
        trace, out = program_trace.trace_of(record), []
        for plane in xplane.device_planes(trace) if trace else ():
            out = [(name, op, s, s + d) for ln in plane["lines"]
                   if ln["name"] == xplane.OP_LINE
                   for name, s, d, op in ln["events"]]
            if out:
                break
        record["op_events"] = out
    return record["op_events"]


def scopes(op_name):
    """The path elements of an ``op_name`` with jax's wrappers taken off:
    ``transpose(jvp(loss))`` reads ``loss``."""
    return [program_trace._WRAPPERS.sub("", part).rstrip(")")
            for part in op_name.split("/")]


def union_ms_per_step(record, keep):
    """Milliseconds a step in which an operation with ``keep(event name,
    op_name)`` ran (a union: overlapping operations count once); None
    where the trace holds no such operation."""
    merged = xplane.merge((s, e) for name, op, s, e in op_events(record)
                          if keep(name, op))
    if not merged:
        return None
    return xplane.total(merged) / 1e6 / record["trace_steps"]
