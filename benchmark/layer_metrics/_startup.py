"""What the ``setup_*`` readers share: the program's own spans
(``paddle_tpu.obs.tracing.finished()``; the readers run in the driver's
process) that END before ``record["window"]["start"]``. The process started
at ``window.start - record["setup_s"]``; every time here is on
``time.monotonic``, the clock ``run.py`` takes both on.

Nothing to read gives ``None``, never a raise and never a short sum: a
parent commit whose program has no bridge from ``jax.monitoring`` (no
``compile.backend`` span: every process compiles something before its
window), and a ring that is full, so that start-up may have fallen out.

Start-up is the main thread's: the driver builds, places, compiles, checks
and probes there. Its seconds before the window are split into classes that
do not overlap, in this order: inside a ``compile.trace`` span (jax tracing
Python), else inside ``compile.lower`` / ``.backend`` (lowering, then a
cache read or XLA), else inside ``nn.init`` / ``train.init_state`` (drawing
and placing, less the compiles those pay for), else inside any other span
of the program (step calls, loader, batches), else inside none.
"""
import re
import threading

from benchmark.harness import xplane

LAYER = "entry / process (utils/compile_cache.py)"
TRACE = "compile.trace"
LOWER = "compile.lower"
BACKEND = "compile.backend"
INIT = ("nn.init", "train.init_state")
_JIT = re.compile(r"^\w+\((.*)\)$")


def spans(record):
    """{"all": every span the ring holds, "before": those that end before
    the window, "main": those of ``before`` on the main thread, "start":
    process start}; None where there is nothing to read."""
    if "startup" not in record:
        record["startup"] = _spans(record)
    return record["startup"]


def _spans(record):
    w, setup_s = record.get("window"), record.get("setup_s")
    if not w or setup_s is None:
        return None
    try:
        from paddle_tpu.obs import tracing
    except ImportError:
        return None
    ring_full = getattr(tracing, "ring_full", None)
    if ring_full is None or ring_full():
        return None
    every = [s for s in tracing.finished() if s.get("t1") is not None]
    before = [s for s in every if s["t1"] <= w["start"]]
    if not any(s["name"] == BACKEND for s in before):
        return None
    main = threading.main_thread().ident
    return {"all": every, "before": before, "start": w["start"] - setup_s,
            "window_start": w["start"],
            "main": [s for s in before if s["thread"] == main]}


def cover(found, names=None, prefix=None):
    """Merged [t0, t1] intervals of the spans with one of ``names`` (or a
    name under ``prefix``; all of them with neither)."""
    return xplane.merge(
        (s["t0"], s["t1"]) for s in found
        if (names is None or s["name"] in names)
        and (prefix is None or s["name"].startswith(prefix)))


def account(startup):
    """The main thread's seconds before the window by class (module
    docstring), and their sum: ``setup_s`` again."""
    main = startup["main"]
    whole = [[startup["start"], startup["window_start"]]]
    trace = cover(main, (TRACE,))
    compiled = xplane.subtract(cover(main, (LOWER, BACKEND)), trace)
    paid = xplane.merge(trace + compiled)
    init = xplane.subtract(cover(main, INIT), paid)
    spanned = xplane.subtract(cover(main), xplane.merge(paid + init))
    parts = {"trace": trace, "compile": compiled, "init": init,
             "other_spans": spanned}
    out = {k: xplane.total(v) for k, v in parts.items()}
    out["unattributed"] = xplane.total(xplane.subtract(whole, cover(main)))
    out["sum"] = sum(out.values())
    return out


def program(fun):
    """``jit(train_step)`` (lowering, backend) and ``train_step`` (tracing)
    name one program."""
    m = _JIT.match(fun or "")
    return m.group(1) if m else (fun or "?")


def compiles(found):
    """[(the ``compile.backend`` span, the ``compile.lower`` span before it
    on its thread for the same program, or None)]."""
    lowered, out = {}, []
    for s in sorted(found, key=lambda s: s["t1"]):
        if s["name"] == LOWER:
            lowered[s["thread"]] = s
        elif s["name"] == BACKEND:
            low = lowered.pop(s["thread"], None)
            same = low and low["attrs"].get("fun") == s["attrs"].get("fun")
            out.append((s, low if same else None))
    return out


def descends_from(span, name, by_id):
    """Whether an ancestor of ``span`` (by ``parent_id``) has this name."""
    seen = set()
    while span is not None and span["span_id"] not in seen:
        seen.add(span["span_id"])
        span = by_id.get(span["parent_id"])
        if span is not None and span["name"] == name:
            return True
    return False
