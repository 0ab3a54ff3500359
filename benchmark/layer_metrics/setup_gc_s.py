"""Seconds before the window that the interpreter's garbage collector held
the process: the program's ``host.gc`` spans (``paddle_tpu.obs.ledger
bridge_gc``: a collection of generation 2, or any that lasted a millisecond)
of ANY thread that end before the window — a collection stops the main
thread whoever triggered it. 0.0 means "measured, none". The note line says
how many and how long by generation, where the seconds lay on the main
thread (inside ``compile.trace`` spans: the share of ``setup_trace_s`` that
is collection; inside ``nn.init`` / ``train.init_state``; inside other
spans; inside none), and what the two counters held at the last such span
before the window: the young, short collections are a total there, not
placed in time, so they stand beside the value and not in it."""
import json

from benchmark.harness import cells, xplane

_startup = cells.load_module("layer_metrics", "_startup")
_hostgc = cells.load_module("layer_metrics", "_hostgc")

LAYER = _startup.LAYER
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(record):
    startup = _startup.spans(record)
    if startup is None or not _hostgc.readable():
        return None
    found = [s for s in startup["before"] if s["name"] == _hostgc.GC]
    note = {"by_generation": _hostgc.by_generation(found),
            "inside_s": inside(startup, found),
            "host_gc_spans_in_ring": sum(s["name"] == _hostgc.GC
                                         for s in startup["all"])}
    print(json.dumps({"setup_gc": dict(note, **counters(found))}),
          flush=True)
    return sum(s["t1"] - s["t0"] for s in found)


def inside(startup, found):
    """The collections' seconds by what the main thread was inside, first
    class wins; the four add up to the value."""
    main = [s for s in startup["main"] if s["name"] != _hostgc.GC]
    out, rest = _hostgc.by_class(
        xplane.merge((s["t0"], s["t1"]) for s in found),
        [("compile.trace", _startup.cover(main, (_startup.TRACE,))),
         ("init", _startup.cover(main, _startup.INIT)),
         ("other_spans", _startup.cover(main))])
    out["none"] = xplane.total(rest)
    return {k: round(v, 9) for k, v in out.items()}


def counters(found):
    """The two counters by generation as the last ``host.gc`` span before
    the window carries them, and how long before the window that was."""
    if not found:
        return {}
    last = max(found, key=lambda s: s["t1"])
    counts, seconds = (last["attrs"].get(k) for k in ("counts", "seconds"))
    if not counts or not seconds:
        return {}
    return {"counters_at_last_span": {
        str(g): {"n": n, "s": round(s, 6)}
        for g, (n, s) in enumerate(zip(counts, seconds))}}
