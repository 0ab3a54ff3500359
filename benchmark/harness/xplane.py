"""From the profiler's trace to device metrics.

``jax.profiler.stop_trace`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but jax. ``load`` turns it
into plain lists (also the form of the recorded trace the tests keep):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Everything below works on that form. Busy time is the UNION of the
intervals in which an operation ran on the device's op line (a sum of
durations would count overlapping events twice and can exceed the window).
"""
import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"              # one op at a time, as the core ran them
ASYNC_LINE = "Async XLA Ops"     # an async op from its start to its done
MODULE_LINE = "XLA Modules"      # one event per program execution
DEVICE_LINES = (OP_LINE, ASYNC_LINE, MODULE_LINE)
HOST_SPAN_PREFIX = "bench:"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path, keep_host_prefix=HOST_SPAN_PREFIX):
    """The op, async-op and module lines of every device plane; host lines
    reduced to the benchmark's own ``bench:*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            if is_device:
                if line.name not in DEVICE_LINES:
                    continue
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(keep_host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIX)]


def op_events(plane, line_name=OP_LINE):
    """[(name, start_ns, end_ns)] of one line of the plane, by start."""
    for line in plane["lines"]:
        if line["name"] == line_name:
            return sorted(((n, s, s + d) for n, s, d in line["events"]),
                          key=lambda e: e[1])
    return []


_LAYOUT = re.compile(r"\{[^}]*\}")
_RHS = re.compile(r"^((?:\([^)]*\))|\S+)\s+([\w\-]+)\(")


def op_name(text):
    """The operation's own name: on the TPU an event's name is the whole
    HLO instruction (``%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce-
    done.1), kind=...``), and its operands may name other operations."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text):
    """A short label that groups the same operation across layers: the
    name without its number, the opcode where the name does not say it, and
    the output's type without layouts: ``fusion (bf16[256,128,3072],
    bf16[256,128,3072])``."""
    name = re.sub(r"[.\d]+$", "", op_name(text))
    if " = " not in text:
        return name[:120]
    m = _RHS.match(_LAYOUT.sub("", text.split(" = ", 1)[1]))
    if not m:
        return name[:120]
    out_type, opcode = m.group(1), m.group(2)
    head = name if opcode in name else f"{name} {opcode}"
    return f"{head} {out_type}"[:120]


def merge(intervals):
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def is_collective(text):
    name = op_name(text)
    return any(mark in name for mark in COLLECTIVE_MARKS)


def busy(trace):
    """Seconds in which an operation ran, and the length of the traced
    window (first op start to last op end), each averaged over the device
    planes that ran anything. None where no operation ran on a device."""
    rows = []
    for plane in device_planes(trace):
        ops = op_events(plane)
        if not ops:
            continue
        merged = merge((s, e) for _, s, e in ops)
        rows.append((total(merged) / 1e9,
                     (merged[-1][1] - merged[0][0]) / 1e9))
    if not rows:
        return None
    return {"busy_s": sum(r[0] for r in rows) / len(rows),
            "window_s": sum(r[1] for r in rows) / len(rows),
            "devices": len(rows)}


def top_ops(trace, n=10):
    """The n groups of device operations with most time in the slice, on
    the first device plane that ran anything: [[label, seconds], ...]; the
    label is ``op_label`` of the trace's own name, so the twelve copies of
    one fusion in twelve layers count as one row."""
    for plane in device_planes(trace):
        ops = op_events(plane)
        if ops:
            by_name = {}
            for name, s, e in ops:
                label = op_label(name)
                by_name[label] = by_name.get(label, 0.0) + (e - s) / 1e9
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
            return [[k, v] for k, v in ranked[:n]]
    return []


def _host_spans(trace):
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            spans.extend((n[len(HOST_SPAN_PREFIX):], s, s + d)
                         for n, s, d in line["events"]
                         if n.startswith(HOST_SPAN_PREFIX))
    return spans


def idle_gaps(trace, n=10):
    """Idle seconds of the first device that ran anything, by what the
    benchmark's loop was doing: each gap between operations goes to the
    ``bench:*`` host span that overlaps it longest (``unlabelled`` where
    none does). [[label, seconds], ...], largest first."""
    for plane in device_planes(trace):
        ops = op_events(plane)
        if not ops:
            continue
        merged = merge((s, e) for _, s, e in ops)
        spans = _host_spans(trace)
        by_label = {}
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            best, best_overlap = "unlabelled", 0.0
            for name, s, e in spans:
                overlap = min(e, s1) - max(s, e0)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            by_label[best] = by_label.get(best, 0.0) + (s1 - e0) / 1e9
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]
    return []


def collectives(trace):
    """Collective time on the first device plane that ran a collective:
    ``total_s`` is the union of the collective operations' intervals (on
    the op line, and from start to done on the async line), ``exposed_s``
    the part of it during which no other operation ran on that device's op
    line. None where the trace holds no collective."""
    for plane in device_planes(trace):
        ops = op_events(plane)
        both = ops + op_events(plane, ASYNC_LINE)
        coll = merge((s, e) for n, s, e in both if is_collective(n))
        if not coll:
            continue
        other = merge((s, e) for n, s, e in ops if not is_collective(n))
        return {"total_s": total(coll) / 1e9,
                "exposed_s": total(subtract(coll, other)) / 1e9,
                "events": sum(1 for n, _, _ in ops if is_collective(n))}
    return None


def excerpt(trace, max_events=400, max_name=240, collective_ms=40.0):
    """A small copy of a trace, for the recorded traces the tests keep: the
    first events of every kept line and, where a device ran collectives,
    every event of its lines in the ``collective_ms`` after the first
    collective began. Names are cut short."""
    planes = []
    for p in trace["planes"]:
        first = min((s for ln in p["lines"] for n, s, _ in ln["events"]
                     if ln["name"] in (OP_LINE, ASYNC_LINE)
                     and is_collective(n)), default=None)
        lines = []
        for ln in p["lines"]:
            keep = ln["events"][:max_events]
            if first is not None:
                lo, hi = first - 5e5, first + collective_ms * 1e6
                keep = keep + [e for e in ln["events"][max_events:]
                               if lo <= e[1] <= hi]
            lines.append({"name": ln["name"],
                          "events": [[n[:max_name], s, d]
                                     for n, s, d in keep]})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
