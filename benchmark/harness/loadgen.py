#!/usr/bin/env python3
"""The open-loop load generator of the serving cells. Started as a process
of its own (``python3 benchmark/harness/loadgen.py <spec.json>``); it
imports neither jax nor the program, so it never takes the chip and never
shares the server's interpreter lock. Wire framing is a copy of
``paddle_tpu/inference/wire_spec.py`` (request ``u32 body_len | u8 cmd |
payload``, cmd 1 = infer; array block ``u8 count`` then per array ``u8
dtype, u8 ndim, i64 dims..., row-major data``; reply ``u32 body_len | u8
status | payload``, status 0 = OK).

Open loop: arrivals are a seeded Poisson process at a FIXED rate; a request
is sent when it is due whether or not earlier ones have been answered, over
a pool of persistent connections (one request in flight on each; a request
that finds no free connection waits in the generator and the wait counts).
Every request is timed from when it was DUE, not from when it was sent, and
the generator reports how late it sent (``late`` = sent - due).

The spec (JSON) holds: port, seed, rate (requests/s of THIS process), start
(``time.monotonic()`` of the window's start, shared by all generators),
seconds, drain_s, connections, rows_mix ({rows: share}), seq, token_low,
token_high, out (path of the result file). The result is a JSON object
with one list per column: due, sent, done, status, rows (times in seconds
relative to ``start``; done < 0 and status -1 = no reply by the end of the
drain).
"""
import json
import selectors
import socket
import struct
import sys
import time

import numpy as np

CMD_INFER = 1
DTYPE_INT32 = 1
STATUS_OK = 0


def encode_request(rows_array):
    """One infer frame carrying one int32 array [rows, seq]."""
    a = np.ascontiguousarray(rows_array, dtype=np.int32)
    payload = (struct.pack("<B", 1)
               + struct.pack("<BB", DTYPE_INT32, a.ndim)
               + struct.pack(f"<{a.ndim}q", *a.shape) + a.tobytes())
    return struct.pack("<IB", 1 + len(payload), CMD_INFER) + payload


def poisson_schedule(rate, seconds, rng):
    """Arrival times in [0, seconds) of a Poisson process of ``rate``/s."""
    if rate <= 0:
        return np.zeros(0)
    n = int(rate * seconds * 1.5 + 50)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:  # the draw was short: extend it
        more = np.cumsum(rng.exponential(1.0 / rate, size=n)) + t[-1]
        t = np.concatenate([t, more])
    return t[t < seconds]


def draw_rows(rows_mix, n, rng):
    sizes = np.asarray([int(k) for k in rows_mix], dtype=np.int64)
    shares = np.asarray([float(v) for v in rows_mix.values()])
    return rng.choice(sizes, size=n, p=shares / shares.sum())


def plan(spec):
    """The seeded schedule: (due times, rows per request, frames by rows).
    Each request's token ids are a seeded draw; to keep the generator's own
    work per request small, a pool of 32 frames per size is built up front
    and request i takes frame i % 32 of its size."""
    rng = np.random.default_rng([int(spec["seed"]), 7])
    due = poisson_schedule(spec["rate"], spec["seconds"], rng)
    rows = draw_rows(spec["rows_mix"], len(due), rng)
    frames = {}
    for size in sorted({int(r) for r in rows}):
        frames[size] = [encode_request(rng.integers(
            spec["token_low"], spec["token_high"],
            size=(size, spec["seq"]), dtype=np.int32)) for _ in range(32)]
    return due, rows, frames


def run(spec):
    due, rows, frames = plan(spec)
    n = len(due)
    sent = np.full(n, -1.0)
    done = np.full(n, -1.0)
    status = np.full(n, -1, dtype=np.int64)
    start = float(spec["start"])
    deadline = start + spec["seconds"] + spec["drain_s"]

    sel = selectors.DefaultSelector()
    free, in_flight, buffers = [], {}, {}
    for _ in range(int(spec["connections"])):
        s = socket.create_connection(("127.0.0.1", int(spec["port"])))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sel.register(s, selectors.EVENT_READ)
        free.append(s)
        buffers[s] = b""

    nxt = 0  # the next request to send, in due order
    while True:
        now = time.monotonic()
        if now >= deadline or (nxt >= n and not in_flight):
            break
        # send everything that is due and finds a free connection
        while nxt < n and free and start + due[nxt] <= now:
            s = free.pop()
            s.sendall(frames[int(rows[nxt])][nxt % 32])
            sent[nxt] = time.monotonic() - start
            in_flight[s] = nxt
            nxt += 1
            now = time.monotonic()
        if nxt < n and free:
            wait = max(0.0, min(start + due[nxt], deadline) - now)
        else:
            wait = max(0.0, deadline - now)
        for sel_key, _ in sel.select(timeout=min(wait, 0.05)):
            s = sel_key.fileobj
            data = s.recv(1 << 16)
            if not data:
                raise ConnectionError("the server closed a connection")
            buf = buffers[s] + data
            if len(buf) >= 4:
                blen = int.from_bytes(buf[:4], "little")
                if len(buf) >= 4 + blen:
                    i = in_flight.pop(s)
                    done[i] = time.monotonic() - start
                    status[i] = buf[4]
                    buf = buf[4 + blen:]
                    free.append(s)
            buffers[s] = buf
    for s in buffers:
        s.close()
    return {"due": due.tolist(), "sent": sent.tolist(), "done": done.tolist(),
            "status": status.tolist(), "rows": [int(r) for r in rows]}


def main():
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    result = run(spec)
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    import os

    os.replace(tmp, spec["out"])


if __name__ == "__main__":
    main()
