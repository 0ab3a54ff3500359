"""The last line of standard output, as the driver reads it."""
import json
import math


def device_record(devices, memory_peak_bytes, busy_s=None, window_s=None):
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}
    if busy_s is not None:
        rec["busy_s"] = busy_s
        rec["window_s"] = window_s
    return rec


def result_line(correct, attempted, failed, metrics, units, device,
                breakdown=None):
    """One JSON object with exactly the contract's keys. ``metrics`` maps a
    name to its value as measured (all digits); a value that is not a finite
    number makes the run incorrect rather than the line unreadable."""
    out = {}
    for name, value in metrics.items():
        value = float(value)
        if not math.isfinite(value):
            correct = False
            continue
        out[name] = {"value": value, "unit": units[name]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": out, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
