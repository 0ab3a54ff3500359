"""What the program says about itself in a traced run: its host spans
(``paddle_tpu.obs.tracing``) and its operations' scopes (``jax.named_scope``
on every ``nn.Layer`` and step phase), for the per-layer readers.

Two sources, both optional: a parent commit without the span layer or the
scopes gives nothing, and every function here then returns ``None`` or an
empty list without raising.

1. ``spans(record)``: the program's in-memory ring
   (``paddle_tpu.obs.tracing.finished()``; the readers run in the driver's
   process), cut to the spans that start inside ``record["window"]``. Both
   clocks are ``time.monotonic``.

2. ``ops(record)``: the traced slice's ``.xplane.pb``, read again for what
   ``xplane.load`` drops. An operation's scope is its HLO ``op_name``; the
   TPU profiler keeps it as the ``tf_op`` stat of the event's *metadata*
   (``XEventMetadata.stats``), which ``jax.profiler.ProfileData`` does not
   hand out (it gives an event's own stats only: established on the v5e,
   PR 23). So the file is read here at the protobuf wire level: ``XSpace
   .planes -> XPlane{name, lines, event_metadata, stat_metadata} ->
   XLine{name, timestamp_ns, events} -> XEvent{metadata_id, offset_ps,
   duration_ps}``; field numbers as in tsl/profiler/protobuf/xplane.proto.
   The result is ``xplane``'s plain form with one more element on an
   operation, and the host lines cut to the program's ``paddle_tpu:*``
   spans::

       {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                    "events": [[name, start_ns, duration_ns, op_name]]}]},
                   {"name": "/host:CPU", "lines": [{"name": "python3",
                    "events": [[name, start_ns, duration_ns]]}]}]}

   A fusion carries the ``op_name`` XLA gave the fused instruction (its
   root's), so a fusion's whole time goes to its root's scope.

A scope is read off ``op_name`` as ``scope_of`` says. The operator's recipe
(no benchmark needed): ``paddle_tpu.utils.profiler.profiler(dir)`` around a
few steps, then ``program_trace.load(xplane.find_xplane(dir))`` and
``scope_table``.
"""
import os
import re

from . import cells, xplane

SPAN_PREFIX = "paddle_tpu:"
PHASES = ("loss", "clip", "optimizer")
#: jax's wrappers around a scope's first element: jvp(...) is the forward
#: pass under differentiation, transpose(jvp(...)) the backward pass
_WRAPPERS = re.compile(r"^(?:(?:jvp|transpose|vmap|checkpoint|remat|"
                       r"rematted_computation|custom_jvp|custom_vjp)\()+")
_MODULE = re.compile(r"^(?:([\w.\-]*):)?([A-Z]\w*)$")


# ------------------------------------------------------------ host spans
def spans(record):
    """The program's finished spans that start inside the window, oldest
    first ([] where the program has no span layer with start times)."""
    w = record.get("window")
    if not w:
        return []
    try:
        from paddle_tpu.obs import tracing
    except ImportError:
        return []
    return [s for s in tracing.finished()
            if "t0" in s and w["start"] <= s["t0"] <= w["end"]]


def trainer_spans(record):
    """(the window's ``train.step`` spans, every span of their thread):
    the loader's reader thread and the workers record ``io.next_batch``
    too, and waiting there is not the trainer waiting."""
    all_spans = spans(record)
    steps = [s for s in all_spans if s["name"] == "train.step"]
    if not steps:
        return [], []
    thread = steps[0]["thread"]
    return steps, [s for s in all_spans if s["thread"] == thread]


def span_ms_per_step(record, names):
    """Milliseconds a step the trainer's thread spent in spans of these
    names, over the program's own count of steps (None without spans)."""
    steps, mine = trainer_spans(record)
    if not steps:
        return None
    total = sum(s["t1"] - s["t0"] for s in mine if s["name"] in names)
    return 1e3 * total / len(steps)


def span_split(record):
    """The window's trainer-thread milliseconds a step by span name, and
    ``train.step``'s self time (its duration less its children's)."""
    from paddle_tpu.obs import tracing

    steps, mine = trainer_spans(record)
    self_s = tracing.self_times(mine)
    split = {}
    for s in mine:
        split[s["name"]] = split.get(s["name"], 0.0) + s["t1"] - s["t0"]
    split["train.step (self)"] = sum(self_s[s["span_id"]] for s in steps)
    return {"steps": len(steps), "ms_per_step": {
        k: round(1e3 * v / len(steps), 4) for k, v in sorted(split.items())}}


# -------------------------------------------------- the protobuf wire form
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(buf, keep_host_prefix):
    name, lines, event_meta, stat_names = "", [], {}, {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif no == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(value) if n == 2), "")
    is_device = name.startswith(xplane.DEVICE_PLANE_PREFIX)
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)

    def describe(meta):
        """(event name, op_name) of one XEventMetadata."""
        ev_name, op_name = "", ""
        for no, v in _fields(meta):
            if no == 2:
                ev_name = _text(v)
            elif no == 5 and is_device:
                stat = dict(_fields(v))
                if stat.get(1) == tf_op and 5 in stat:
                    # "<op_name>:<op type>", the type empty under jax
                    op_name = _text(stat[5]).rpartition(":")[0]
        return ev_name, op_name

    described, out = {}, []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for no, v in _fields(line):
            if no == 2:
                line_name = _text(v)
            elif no == 3:
                t0_ns = v
            elif no == 4:
                events.append(v)
        if is_device and line_name != xplane.OP_LINE:
            continue
        rows = []
        for ev in events:
            f = dict(_fields(ev))
            mid = f.get(1, 0)
            if mid not in described:
                described[mid] = describe(event_meta.get(mid, b""))
            ev_name, op_name = described[mid]
            row = [ev_name, t0_ns + f.get(2, 0) / 1e3, f.get(3, 0) / 1e3]
            if is_device:
                rows.append(row + [op_name])
            elif ev_name.startswith(keep_host_prefix):
                rows.append(row)
        if rows:
            out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out} if out else None


def load(path, keep_host_prefix=SPAN_PREFIX):
    """The ``XLA Ops`` line of every device plane with each operation's
    ``op_name``, and the host lines' ``paddle_tpu:*`` spans."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = [_plane(v, keep_host_prefix)
              for no, v in _fields(buf) if no == 1]
    return {"planes": [p for p in planes if p]}


def trace_of(record):
    """The traced slice in the form above, read once a record (None where
    the run was not traced or wrote no file). A test may put a recorded
    one under ``record["program_trace"]``."""
    if "program_trace" not in record:
        path = None
        if record.get("trace") and record.get("cell"):
            path = xplane.find_xplane(os.path.join(
                cells.ROOT, ".benchmark_out", record["cell"]["name"],
                "trace"))
        record["program_trace"] = load(path) if path else None
    return record["program_trace"]


# ------------------------------------------------------------ the scopes
def scope_of(op_name):
    """What ``op_name`` says of an operation:

    ``phase``    ``fwd`` / ``bwd`` under a module or ``loss`` scope
                 (``bwd`` where jax wrapped it in ``transpose(``), or
                 ``clip`` / ``optimizer``; None where the operation
                 carries no program scope
    ``modules``  the module scopes from the root in, each (attribute,
                 class): ``[("", "PackedMLM"), ("inner",
                 "BertForPretraining"), ..., ("norm1", "LayerNorm")]``
    """
    modules, phase = [], None
    for part in op_name.split("/"):
        bare = _WRAPPERS.sub("", part).rstrip(")")
        if bare in PHASES and phase is None and not modules:
            phase = bare
        else:
            m = _MODULE.match(bare)
            if m:
                modules.append((m.group(1) or "", m.group(2)))
    if phase in (None, "loss") and (modules or phase):
        phase = "bwd" if "transpose(" in op_name else "fwd"
    return {"phase": phase, "modules": modules}


def program_has_scopes():
    """Whether this checkout's program scopes its operations at all (the
    parent of PR 23 does not): tells "nothing to read" from "an executable
    without scopes", which ``device_scoped_pct`` reports as 0."""
    try:
        from paddle_tpu.nn import Layer
    except ImportError:
        return False
    return hasattr(Layer, "scope_name")


def ops(record):
    """[(scope, start_ns, end_ns)] of the operations on the first device
    that ran anything in the traced slice, by start (``scope`` as
    ``scope_of`` gives it); None without a readable trace."""
    if "program_ops" not in record:
        trace, out = trace_of(record), None
        for plane in xplane.device_planes(trace) if trace else ():
            events = [e for ln in plane["lines"] for e in ln["events"]
                      if ln["name"] == xplane.OP_LINE]
            if events:
                out = sorted(((scope_of(op), s, s + d)
                              for _, s, d, op in events),
                             key=lambda e: e[1])
                break
        record["program_ops"] = out
    return record["program_ops"]


def union_ms_per_step(record, keep):
    """Milliseconds a step in which an operation with ``keep(scope)`` ran
    on the device (a union: overlapping operations count once), over the
    slice's steps. None without scopes in the trace."""
    all_ops = ops(record)
    if not all_ops or not any(scope["phase"] for scope, _, _ in all_ops):
        return None
    merged = xplane.merge((s, e) for scope, s, e in all_ops if keep(scope))
    return xplane.total(merged) / 1e6 / record["trace_steps"]


def innermost(scope):
    """The class of the innermost module scope ('' for none)."""
    return scope["modules"][-1][1] if scope["modules"] else ""


def host_spans(trace):
    """[(name, start_ns, end_ns)] of the program's spans in the trace."""
    return [(n[len(SPAN_PREFIX):], s, s + d)
            for plane in trace["planes"]
            if not plane["name"].startswith(xplane.DEVICE_PLANE_PREFIX)
            for line in plane["lines"] for n, s, d in line["events"]
            if n.startswith(SPAN_PREFIX)]


def scope_table(record, n=10):
    """The slice's device time by innermost module scope and phase:
    [[label, ms a step], ...], largest first; ``label`` is
    ``fwd attr:Class`` with numbered attributes (the twelve encoder
    layers) folded into one row."""
    by_label = {}
    for scope, s, e in ops(record) or ():
        if scope["modules"]:
            attr, cls = scope["modules"][-1]
            attr = "" if attr.isdigit() else attr
            label = f"{scope['phase']} " + (f"{attr}:{cls}" if attr else cls)
        else:
            label = scope["phase"] or "unscoped"
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e6 / record["trace_steps"]] for k, v in ranked]


def excerpt(trace, every=20, max_name=100):
    """A small copy of a loaded trace for the recorded traces the tests
    keep: every ``every``-th operation of a device line (so every phase of
    every step is still there), names cut short; host spans whole."""
    planes = []
    for p in trace["planes"]:
        device = p["name"].startswith(xplane.DEVICE_PLANE_PREFIX)
        planes.append({"name": p["name"], "lines": [
            {"name": ln["name"],
             "events": [[e[0][:max_name]] + list(e[1:])
                        for e in ln["events"][::every if device else 1]]}
            for ln in p["lines"]]})
    return {"planes": planes}

