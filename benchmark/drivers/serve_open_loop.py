"""serve_open_loop: a one-shot model behind the serving engine, under open-
loop load.

The served layer comes from the configuration's ``build_serve``; it is saved
with ``jit.save`` and served by ``serve_model(**traffic["serve_model"])`` in
THIS process (the one that holds the chip). Load comes from
``traffic["generators"]`` processes of ``harness/loadgen.py``, which import
neither jax nor the program; the benchmark sets no interpreter or engine
knob a caller of ``serve_model`` would not set.

Set-up (not measured): build, save, load + warm-up (the engine compiles its
declared buckets), the correctness check through the socket, a few requests
of every size, start of the generators. The window is ``seconds`` of
arrivals; a request counts where it was DUE inside the window, and is timed
from when it was due to its complete reply frame. After the window the
generators wait ``drain_s`` for late replies. With ``--trace 1`` the
profiler runs for ``trace_seconds`` inside the window.
"""
import json
import os
import socket
import struct
import subprocess
import sys
import time

import numpy as np

from benchmark.harness import loadgen, memory, stats, tracing
from benchmark.harness.datasets import bound, field_shapes

CMD_STATS, CMD_METRICS = 5, 6


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("the server closed the connection")
        buf += chunk
    return bytes(buf)


def round_trip(port, frame):
    """One request frame on a connection of its own; (status, body)."""
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.sendall(frame)
        head = _recv_exact(s, 4)
        body = _recv_exact(s, int.from_bytes(head, "little"))
    return body[0], body[1:]


def wire_command(port, cmd):
    return round_trip(port, struct.pack("<IB", 1, cmd))


def infer(port, rows):
    """The float32 reply for int32 ``rows``: the first array of the reply's
    array block (a copy of wire_spec's layout: u8 count, then u8 dtype,
    u8 ndim, i64 dims, data)."""
    status, body = round_trip(port, loadgen.encode_request(rows))
    if status != loadgen.STATUS_OK:
        raise RuntimeError(f"infer status {status}: {body[:200]!r}")
    code, ndim = struct.unpack_from("<BB", body, 1)
    dims = struct.unpack_from(f"<{ndim}q", body, 3)
    if code != 0:
        raise ValueError(f"reply dtype code {code}, expected float32 (0)")
    return np.frombuffer(body, np.float32, int(np.prod(dims)),
                         3 + 8 * ndim).reshape(dims)


def prometheus_totals(text):
    """{series name: sum over its label sets} of a Prometheus exposition."""
    totals = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        name = name_part.split("{", 1)[0]
        try:
            totals[name] = totals.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return totals


def counters(port):
    """The program's counters now: its Prometheus series (wire command
    ``metrics``) summed over labels, and the engine's ``stats``."""
    replies = []
    for cmd in (CMD_METRICS, CMD_STATS):
        status, body = wire_command(port, cmd)
        if status != loadgen.STATUS_OK:
            raise RuntimeError(f"wire command {cmd} returned status {status}")
        replies.append(body.decode("utf-8"))
    totals = prometheus_totals(replies[0])
    engine = json.loads(replies[1])
    totals["engine_compiles"] = engine["compiles"]
    totals["engine_shed"] = (engine["shed_count"] + engine["quarantine_shed"]
                             + engine["deadline_expired"])
    return totals, engine


def setup(ctx):
    """Build, save, serve, check, warm. Returns the handle ``offer`` uses."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.server import serve_model

    traffic, sizes = ctx.traffic, ctx.sizes
    shapes = field_shapes(traffic)
    built = ctx.config.build_serve(ctx.seed, sizes, shapes)
    prefix = os.path.join(ctx.out_dir, "model", "served")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    paddle.jit.save(built["layer"], prefix, input_spec=built["input_spec"])
    server = serve_model(prefix, **traffic["serve_model"])
    _, engine = counters(server.port)

    # correctness, through the socket: 8 seeded rows in requests of 1..4
    rng = np.random.default_rng([ctx.seed, 11])
    field = traffic["fields"][0]
    low, high = bound(field["low"], sizes), bound(field["high"], sizes)
    rows = rng.integers(low, high, size=(8,) + tuple(field["shape"]),
                        dtype=np.int32)
    served = np.concatenate([infer(server.port, rows[a:b])
                             for a, b in ((0, 1), (1, 2), (2, 4), (4, 8))])
    check = ctx.config.check_serve(built, ctx.reference, sizes, rows, served,
                                   ctx.devices[0].platform)
    for size in sorted(int(k) for k in traffic["rows_mix"]):  # host path, warm
        for _ in range(3):
            infer(server.port, np.repeat(rows[:1], size, axis=0))
    ctx.log({"serve_check": check, "declared_buckets":
             engine["declared_buckets"], "warmup_compiles": engine["compiles"]})
    return {"server": server, "check": check, "low": low,
            "high": high, "seq": int(field["shape"][0]), "round": 0}


def offer(ctx, handle, rate_per_s, seconds, trace=False):
    """One window of open-loop load at ``rate_per_s``; returns the requests
    (arrays, times in seconds from the window's start) and the counters
    before and after the window."""
    traffic = ctx.traffic
    port = handle["server"].port
    gens = int(traffic["generators"])
    handle["round"] += 1
    start = time.monotonic() + float(traffic["lead_s"])
    procs, outs = [], []
    for g in range(gens):
        out = os.path.join(ctx.out_dir, f"gen{handle['round']}_{g}.json")
        if os.path.exists(out):
            os.unlink(out)
        spec = {"port": port, "seed": ctx.seed * 1000 + handle["round"] * 16 + g,
                "rate": rate_per_s / gens, "start": start, "seconds": seconds,
                "drain_s": traffic["drain_s"],
                "connections": max(1, int(traffic["connections"]) // gens),
                "rows_mix": traffic["rows_mix"], "seq": handle["seq"],
                "token_low": handle["low"], "token_high": handle["high"],
                "out": out}
        spec_path = out + ".spec"
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(ctx.root, "benchmark", "harness",
                                          "loadgen.py"), spec_path]))
        outs.append(out)
    try:
        time.sleep(max(0.0, start - time.monotonic()))
        before, _ = counters(port)
        meter_before = ctx.meter.snapshot()
        trace_data = None
        if trace:
            time.sleep(max(0.0, start + traffic["trace_from_s"]
                           - time.monotonic()))
            trace_dir = os.path.join(ctx.out_dir, "trace")
            tracing.start(trace_dir)
            time.sleep(traffic["trace_seconds"])
            trace_data = tracing.stop_and_load(trace_dir)
        time.sleep(max(0.0, start + seconds - time.monotonic()))
        after, _ = counters(port)
        meter_after = ctx.meter.snapshot()
        for p in procs:
            if p.wait(seconds + traffic["drain_s"] + 60) != 0:
                raise RuntimeError(f"a load generator exited {p.returncode}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    cols = {k: [] for k in ("due", "sent", "done", "status", "rows")}
    for out in outs:
        with open(out) as f:
            got = json.load(f)
        for k in cols:
            cols[k].extend(got[k])
    req = {k: np.asarray(v) for k, v in cols.items()}
    return {"requests": req, "start": start, "seconds": seconds,
            "counters_before": before, "counters_after": after,
            "window_compiles": (meter_after["compiles"]
                                - meter_before["compiles"]
                                + after["engine_compiles"]
                                - before["engine_compiles"]),
            "setup_compile_s": meter_before["compile_s"], "trace": trace_data}


def summarise(traffic, seq, window):
    """The end-to-end numbers of one window. A request that failed, was
    shed or got no reply by the end of the drain counts as slower than any
    limit: its time is the whole window plus the drain."""
    req, seconds = window["requests"], window["seconds"]
    ok = (req["status"] == loadgen.STATUS_OK) & (req["done"] >= 0)
    worst_ms = (seconds + traffic["drain_s"]) * 1e3
    first_reply_ms = np.where(ok, (req["done"] - req["due"]) * 1e3, worst_ms)
    sent = req["sent"] >= 0
    late_ms = np.where(sent, (req["sent"] - req["due"]) * 1e3, worst_ms)
    good = ok & (first_reply_ms <= traffic["latency_limit_ms"])
    n = len(req["due"])
    return {
        "attempted": n, "failed": int((~ok).sum()),
        "offered_per_s": n / seconds,
        "completed_share": float(ok.sum()) / max(n, 1),
        # completions keep up with arrivals: replies that came INSIDE the
        # window over the requests due in it (the drain is not counted)
        "completed_in_window_share": float(
            (ok & (req["done"] <= seconds)).sum()) / max(n, 1),
        "first_reply_ms_p50": stats.percentile(first_reply_ms, 50),
        "first_reply_ms_p95": stats.percentile(first_reply_ms, 95),
        "serve_good_tokens_per_s": float(
            (req["rows"][good] * seq).sum()) / seconds,
        "gen_late_ms_p95": stats.percentile(late_ms, 95),
        "first_reply_ms": first_reply_ms, "late_ms": late_ms,
    }


def run(ctx):
    traffic = ctx.traffic
    handle = setup(ctx)
    try:
        window = offer(ctx, handle, traffic["rate_per_s"], ctx.seconds,
                       trace=ctx.trace)
        stats_now = memory.runtime_stats(ctx.devices)
    finally:
        handle["server"].stop()
    s = summarise(traffic, handle["seq"], window)
    ctx.log({"memory_stats": stats_now,
             "summary": {k: v for k, v in s.items()
                         if not isinstance(v, np.ndarray)}})
    late_ok = s["gen_late_ms_p95"] <= traffic["latency_limit_ms"] / 10.0
    return {
        "correct": bool(handle["check"]["ok"] and late_ok
                        and window["window_compiles"] == 0),
        "attempted": s["attempted"], "failed": s["failed"],
        "t_window_start": window["start"],
        "end_to_end": {k: s[k] for k in (
            "first_reply_ms_p50", "first_reply_ms_p95",
            "serve_good_tokens_per_s")},
        "memory_peak_bytes": memory.peak_bytes(stats_now, []),
        "record": {
            "window": {"start": window["start"],
                       "end": window["start"] + window["seconds"],
                       "seconds": window["seconds"]},
            "chips": len(ctx.devices), "spans": ctx.recorder.spans,
            "first_reply_ms": s["first_reply_ms"], "late_ms": s["late_ms"],
            "counters_before": window["counters_before"],
            "counters_after": window["counters_after"],
            "setup_compile_s": window["setup_compile_s"],
            "window_compiles": window["window_compiles"],
            "trace": window["trace"], "programs": [],
        },
    }
