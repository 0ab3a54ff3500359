"""Compile ledger: a record of every XLA compile the process pays.

On TPU, unexpected recompiles are the dominant silent performance
regression (tracelint's TPU101–TPU104 catch them statically; the ledger
catches them at runtime), and compiled-program *structure* — op mix,
cost-analysis FLOPs/bytes — is a chip-independent count that repeats
exactly from run to run. Every entry records:

    key          caller-chosen identity (e.g. "serving/bucket8")
    kind         "aot" (jax AOT .lower().compile()), "callable", ...
    duration_s   wall-clock compile time
    flops / bytes_accessed   from ``compiled.cost_analysis()``
    op_counts    {hlo_opcode: n} parsed from ``compiled.as_text()``
    fingerprint  sha256 over the ordered opcode sequence — a
                 *structural* HLO identity that ignores value names and
                 literal payloads, so two compiles of the same program
                 shape match even when buffer ids differ

The serving suites read it to count real compiles apart from store
loads (tests/test_artifact_serving.py, test_quant_serving.py,
test_sharded_serving.py); the counts are exact, never a speed.
"""
import hashlib
import re
import threading
import time

from . import metrics as _metrics
from . import tracing as _tracing

_COMPILES = _metrics.counter(
    "paddle_compile_events_total",
    "XLA compile events recorded in the compile ledger",
    labelnames=("kind",))
_COMPILE_SECONDS = _metrics.histogram(
    "paddle_compile_seconds",
    "Duration of recorded compile events",
    buckets=_metrics.log_buckets(0.001, 4.0, 10))

_OPCODE_RE = re.compile(r"^[a-zA-Z][\w-]*")


def _strip_hlo_type(rhs):
    """Drop the leading result type from an HLO instruction RHS —
    either a whitespace-free shape like ``f32[8,4]{1,0}`` or a
    parenthesized tuple type like ``(f32[2]{0}, s32[])`` (which
    contains spaces, so token-splitting alone would mis-parse)."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[i + 1:].lstrip()
        return ""
    parts = rhs.split(None, 1)
    return parts[1] if len(parts) > 1 else ""


def hlo_typed_opcodes(hlo_text):
    """Ordered ``opcode:result_dtype`` sequence of every instruction in
    an HLO module text dump (computation headers and metadata lines are
    skipped) — ``convert:f32``, ``parameter:s8``, ``dot:f32``;
    tuple-typed results report ``tuple``. The ONE parsing pass: the
    untyped view is a projection (:func:`hlo_opcodes`). The dtype
    dimension is what tests/test_quant_serving.py reads: a
    ``parameter:s8`` / ``parameter:bf16`` count proves
    reduced-precision weights actually reached XLA instead of silently
    promoting to f32 upstream of the lowering."""
    ops = []
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        rhs_full = line.split(" = ", 1)[1].lstrip()
        if rhs_full.startswith("("):
            dtype = "tuple"
        else:
            head = rhs_full.split(None, 1)[0]
            dtype = head.split("[", 1)[0]
        rhs = _strip_hlo_type(rhs_full)
        m = _OPCODE_RE.match(rhs)
        if m and "(" in rhs[m.end():m.end() + 1]:
            ops.append(f"{m.group(0)}:{dtype}")
    return ops


def hlo_opcodes(hlo_text):
    """Ordered opcode sequence of every instruction in an HLO module
    text dump — the dtype-less projection of
    :func:`hlo_typed_opcodes` (opcode names never contain ``:``), so
    there is exactly one parser to maintain."""
    return [op.partition(":")[0] for op in hlo_typed_opcodes(hlo_text)]


def hlo_fingerprint(opcodes):
    """Structural identity: sha256 over the ordered opcode sequence."""
    h = hashlib.sha256()
    for op in opcodes:
        h.update(op.encode("ascii", "replace"))
        h.update(b"\n")
    return h.hexdigest()[:16]


def analyze_compiled(compiled):
    """Best-effort structural + cost analysis of a jax AOT ``Compiled``.

    Never raises: backends without as_text()/cost_analysis() yield a
    partial record (the ledger must not break serving when XLA's
    introspection surface shifts under a jax upgrade)."""
    out = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            flops = cost.get("flops")
            if flops is not None:
                out["flops"] = float(flops)
            acc = cost.get("bytes accessed")
            if acc is not None:
                out["bytes_accessed"] = float(acc)
    except Exception:  # noqa: BLE001 — introspection is best-effort
        pass
    try:
        # ONE parse of the HLO text; the untyped view (op_counts,
        # n_ops, the structural fingerprint — all byte-compatible with
        # pre-quant baselines) is a projection of the typed sequence
        typed_ops = hlo_typed_opcodes(compiled.as_text())
        ops = [op.partition(":")[0] for op in typed_ops]
        counts = {}
        for op in ops:
            counts[op] = counts.get(op, 0) + 1
        out["op_counts"] = counts
        out["n_ops"] = len(ops)
        out["fingerprint"] = hlo_fingerprint(ops)
        typed = {}
        for op in typed_ops:
            typed[op] = typed.get(op, 0) + 1
        # opcode:result_dtype counts — the reduced-precision evidence
        # (parameter:s8 / parameter:bf16 / convert:f32) that
        # tests/test_quant_serving.py asserts on
        out["typed_op_counts"] = typed
    except Exception:  # noqa: BLE001
        pass
    return out


class CompileLedger:
    """Append-only, bounded record of compile events."""

    def __init__(self, cap=1024):
        self._lock = threading.Lock()
        self._events = []
        self._cap = cap

    def record(self, key, duration_s=None, compiled=None, kind="aot",
               extra=None):
        """Record one compile event; returns the event dict."""
        ev = {"key": str(key), "kind": kind, "ts": time.time()}
        if duration_s is not None:
            ev["duration_s"] = round(float(duration_s), 6)
        if compiled is not None:
            ev.update(analyze_compiled(compiled))
        if extra:
            ev.update(extra)
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._cap:
                del self._events[:len(self._events) - self._cap]
        _COMPILES.inc(kind=kind)
        if duration_s is not None:
            _COMPILE_SECONDS.observe(float(duration_s))
            _tracing.observe(f"compile:{key}", float(duration_s))
        return ev

    def events(self, key_prefix=None):
        with self._lock:
            evs = list(self._events)
        if key_prefix is not None:
            evs = [e for e in evs if e["key"].startswith(key_prefix)]
        return evs

    def totals(self, key_prefix=None):
        """Aggregate view: compile count, summed flops/bytes, merged
        op counts."""
        evs = self.events(key_prefix)
        op_counts = {}
        flops = 0.0
        acc = 0.0
        for e in evs:
            flops += e.get("flops", 0.0)
            acc += e.get("bytes_accessed", 0.0)
            for op, n in e.get("op_counts", {}).items():
                op_counts[op] = op_counts.get(op, 0) + n
        return {"compiles": len(evs), "flops": flops,
                "bytes_accessed": acc, "op_counts": op_counts,
                "n_ops": sum(op_counts.values())}

    def reset(self):
        with self._lock:
            self._events = []


#: Default process ledger (the serving engine's AOT compiles land here).
LEDGER = CompileLedger()
