#!/usr/bin/env python3
"""What a cell's last traced slice spent where, for PERF.md section 5: the
device's busy milliseconds a step by innermost module scope and phase (every
row, not the ten of ``device_scoped_pct``'s note), by ``jax.named_scope``
inside the expert layer, and the longest single operations by name.

    python3 benchmark/tools/trace_scopes.py <cell> [steps=6] [top=25]

reads ``.benchmark_out/<cell>/trace`` (a ``--trace 1`` run wrote it)."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, program_trace, xplane  # noqa: E402


def main():
    cell = sys.argv[1]
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    top = int(sys.argv[3]) if len(sys.argv) > 3 else 25
    path = xplane.find_xplane(os.path.join(
        ROOT, ".benchmark_out", cell, "trace"))
    record = {"program_trace": program_trace.load(path), "trace_steps": steps}
    ops = cells.load_module("layer_metrics", "_op_names")
    events = ops.op_events(record)
    busy = xplane.total(xplane.merge((s, e) for _, _, s, e in events))
    print(json.dumps({"cell": cell, "steps": steps, "operations": len(events),
                      "busy_ms_per_step": busy / 1e6 / steps}))
    print(json.dumps({"by_module_and_phase_ms_per_step":
                      program_trace.scope_table(record, n=1000)}))
    inside, by_op = {}, {}
    for name, op, s, e in events:
        for part in ops.scopes(op):
            if part.startswith("moe.") or part.startswith("flash_stream_"):
                inside[part] = inside.get(part, 0.0) + e - s
        key = name.split(" = ")[0].rstrip("0123456789.") + "  " + "/".join(
            op.split("/")[-3:])
        by_op[key] = by_op.get(key, 0.0) + e - s
    print(json.dumps({"by_named_scope_ms_per_step": {
        k: v / 1e6 / steps for k, v in sorted(inside.items())}}))
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    print(json.dumps({"longest_operations_ms_per_step": [
        [k, v / 1e6 / steps] for k, v in ranked]}))


if __name__ == "__main__":
    main()
