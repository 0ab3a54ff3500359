"""What the three readers of the host's pauses share (``setup_gc_s``,
``host_gc_ms_per_step``, ``host_stall_ms_max``): the program's ``host.gc``
spans (``paddle_tpu.obs.ledger bridge_gc``: a collection of generation 2, or
any that lasted a millisecond, on ``time.monotonic``; the readers run in the
driver's process) and the account of a stretch of the trainer's thread by
class.

Nothing to read gives ``None``, never a raise and never a short sum: a
parent commit whose program has no bridge from ``gc.callbacks`` (no
``paddle_gc_collections_total`` on its metrics registry: 0.0 has to mean
"measured, none", never "cannot see"), and a ring that is full.
"""
import statistics

from benchmark.harness import program_trace, xplane

GC = "host.gc"
COUNTER = "paddle_gc_collections_total"
STEP = "train.step"


def readable():
    """Whether the program records its collections and its ring still
    holds all of the run."""
    try:
        from paddle_tpu.obs import metrics, tracing
    except ImportError:
        return False
    ring_full = getattr(tracing, "ring_full", None)
    if ring_full is None or ring_full():
        return False
    return any(f.name == COUNTER for f in metrics.REGISTRY.collect())


def by_generation(found):
    """{generation: {n, s, max_ms}} of ``host.gc`` spans."""
    out = {}
    for s in found:
        row = out.setdefault(str(s["attrs"].get("generation", "?")),
                             {"n": 0, "s": 0.0, "max_ms": 0.0})
        row["n"] += 1
        row["s"] += s["t1"] - s["t0"]
        row["max_ms"] = max(row["max_ms"], 1e3 * (s["t1"] - s["t0"]))
    return {g: {k: round(v, 6) for k, v in row.items()}
            for g, row in sorted(out.items())}


def window(record):
    """{"steps": the window's ``train.step`` spans by start, "mine": every
    span of their thread, "gc": every ``host.gc`` span of any thread (a
    collection stops them all), "in_window": those that start in it}; None
    where there is nothing to read."""
    if "host_window" not in record:
        record["host_window"] = _window(record)
    return record["host_window"]


def _window(record):
    if not readable():
        return None
    steps, _ = program_trace.trainer_spans(record)
    if not steps:
        return None
    from paddle_tpu.obs import tracing

    w, thread = record["window"], steps[0]["thread"]
    every = [s for s in tracing.finished() if s.get("t1") is not None]
    gcs = [s for s in every if s["name"] == GC]
    return {"steps": sorted(steps, key=lambda s: s["t0"]),
            "mine": [s for s in every if s["thread"] == thread
                     and s["name"] != GC],
            "gc": gcs,
            "in_window": [s for s in gcs
                          if w["start"] <= s["t0"] <= w["end"]]}


def by_class(target, classes):
    """({label: seconds of the merged intervals ``target`` inside that
    class's merged cover}, what no class covers): the first class wins, so
    the seconds and the rest add up to ``target``."""
    out, rest = {}, target
    for label, cover in classes:
        left = xplane.subtract(rest, cover)
        out[label] = xplane.total(rest) - xplane.total(left)
        rest = left
    return out, rest


def account(lo, hi, found, bench_spans=()):
    """Milliseconds of [lo, hi] by class, first class wins, summing to its
    length: ``host.gc`` (any thread), ``compile`` (a recompile), the
    loader's ``io.next_batch.wait`` and the rest of ``io.next_batch``,
    ``spmd.shard_batch``, ``train.step`` by child and its self time, other
    spans of the trainer's thread, and ``uncovered``: no span of the
    program (the caller waiting for the device, or the host not running).
    ``uncovered_by`` splits that by the benchmark's own spans."""
    mine = [s for s in found["mine"] if s["t1"] > lo and s["t0"] < hi]

    def cover(spans):
        return xplane.merge((s["t0"], s["t1"]) for s in spans)

    def named(*names, prefix=None):
        return cover(s for s in mine if s["name"] in names
                     or (prefix and s["name"].startswith(prefix)))

    children = sorted({s["name"] for s in mine
                       if s["name"].startswith(STEP + ".")})
    out, holes = by_class([[lo, hi]], [
        (GC, cover(found["gc"])),
        ("compile", named(prefix="compile.")),
        ("io.next_batch.wait", named("io.next_batch.wait")),
        ("io.next_batch", named("io.next_batch", prefix="io.next_batch.")),
        ("spmd.shard_batch", named("spmd.shard_batch"))]
        + [(child, named(child)) for child in children]
        + [(STEP + " (self)", named(STEP)), ("other spans", cover(mine))])
    out["uncovered"] = xplane.total(holes)
    by, rest = by_class(holes, [
        ("bench:" + name, xplane.merge(
            (t0, t1) for n, t0, t1 in bench_spans if n == name))
        for name in sorted({n for n, _, _ in bench_spans})])
    by["none"] = xplane.total(rest)

    def ms(seconds):
        return {k: round(1e3 * v, 3) for k, v in seconds.items() if v > 0}

    return {"ms_by_class": dict(ms(out), uncovered=round(
        1e3 * out["uncovered"], 3)), "uncovered_by": ms(by)}


def stretches(record, found, longest=3):
    """[{where, at_s, ms, ms_by_class, uncovered_by}]: the ``longest``
    intervals between the starts of consecutive ``train.step`` spans, the
    median one (what a stalled one is held against), and the window's two
    ends whatever their length — window start to the first step's start,
    the last step's end to the window's end, where a stall is charged in
    full. (The loop runs one step ahead of the device, so the end stretch
    holds the device's last two steps: hold it against two median
    intervals, not against 0.)"""
    w, steps = record["window"], found["steps"]
    bench = [s for s in record.get("spans") or () if s[2] >= w["start"]]
    pairs = sorted(zip(steps, steps[1:]),
                   key=lambda p: p[0]["t0"] - p[1]["t0"])
    picked = [("interval", a["t0"], b["t0"]) for a, b in pairs[:longest]]
    if pairs:
        a, b = pairs[len(pairs) // 2]
        picked.append(("median interval", a["t0"], b["t0"]))
    picked += [("window start", w["start"], steps[0]["t0"]),
               ("window end", steps[-1]["t1"], w["end"])]
    return [dict({"where": where, "at_s": round(lo - w["start"], 4),
                  "ms": round(1e3 * (hi - lo), 3)},
                 **account(lo, hi, found, bench))
            for where, lo, hi in picked]


def stall_ms(found):
    """The longest interval between consecutive step starts less their
    median, ms (None with fewer than two intervals)."""
    starts = [s["t0"] for s in found["steps"]]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    if len(gaps) < 2:
        return None
    return 1e3 * (max(gaps) - statistics.median(gaps))
