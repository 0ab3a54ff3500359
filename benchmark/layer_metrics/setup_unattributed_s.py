"""``setup_s`` less the union of the main thread's program spans that end
before the window (regions and bridged compiles alike): imports and the
TPU runtime's start before the first span, and whatever the program or the
benchmark does at start-up outside every span (the reference check's own
host work, batches made in the trainer's process). The account of the whole
``setup_s`` by class, the spans the ring holds and the largest uncovered
stretches (the first is the time before the first span; each with the spans
on either side) go out as a note line: they say what to span next."""
import json

from benchmark.harness import cells, xplane

_startup = cells.load_module("layer_metrics", "_startup")

LAYER = _startup.LAYER
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(record):
    startup = _startup.spans(record)
    if startup is None:
        return None
    parts = _startup.account(startup)
    print(json.dumps({"setup_account_s": {
        k: round(v, 3) for k, v in parts.items()},
        "setup_s": round(record["setup_s"], 3),
        "spans_in_ring": len(startup["all"]),
        "setup_gaps": gaps(startup)}), flush=True)
    return parts["unattributed"]


def gaps(startup, n=5):
    """The ``n`` longest stretches of the main thread before the window
    that no span covers: seconds, start (seconds after process start), and
    the names of the (outermost) spans that end before and start after
    it."""
    main, start = startup["main"], startup["start"]
    covered = _startup.cover(main)
    holes = xplane.subtract([[start, startup["window_start"]]], covered)
    out = []
    for lo, hi in sorted(holes, key=lambda h: h[0] - h[1])[:n]:
        ends = [s for s in main if s["t1"] <= lo + 1e-9]
        starts = [s for s in main if s["t0"] >= hi - 1e-9]
        out.append({
            "s": round(hi - lo, 3), "at_s": round(lo - start, 3),
            "after": max(ends, key=lambda s: (s["t1"], -s["t0"]))["name"]
            if ends else "process start",
            "before": min(starts, key=lambda s: (s["t0"], -s["t1"]))["name"]
            if starts else "window"})
    return out
