"""Load generator: the benchmark's client, run as a child process.

    python bench/loadgen.py      (started by bench/harness.py, never by hand)

It never imports JAX, so it cannot take the chip and its Python does not
share the server's interpreter lock.  It speaks the server's wire
protocol (``bench/wire.py``) over loopback TCP.

Talk with the parent, over the child's stdin and stdout:
  1. stdin: one JSON line (host, port, seed, seconds, warm-up seconds,
     the traffic mix, pool and tenant sizes), then the context pool as
     raw int32 bytes;
  2. the child connects, sends the warm-up traffic (the same law on its
     own seed stream, not recorded), and writes ``READY``;
  3. stdin: ``GO <t0>`` — the window's start on the shared
     ``time.monotonic`` clock; the child sends the window's traffic,
     waits for every reply (at most ``grace`` seconds past the close),
     and writes ``RESULT <n>`` and an ``.npz`` of n bytes: per request
     its scheduled and actual send time, receipt time, status (-1 = no
     reply), K, context, tenant and the reply's scores and slots.
"""
from __future__ import annotations

import gc
import io
import json
import os
import selectors
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import traffic, wire  # noqa: E402

GRACE_S = 60.0


class Record:
    """Preallocated per-request outcome arrays for ``n`` requests."""

    def __init__(self, n: int, max_k: int):
        self.sched = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.recv = np.full(n, np.nan)
        self.status = np.full(n, -1, np.int16)
        self.served = np.zeros(n, np.int16)
        self.scores = np.zeros((n, max_k), np.float32)
        self.slots = np.full((n, max_k), -1, np.int32)

    def reply(self, i, now, status, scores, slots) -> None:
        self.recv[i] = now
        self.status[i] = status
        if status == 0:
            self.served[i] = len(scores)
            self.scores[i, :len(scores)] = scores
            self.slots[i, :len(slots)] = slots


def _connect(host, port, n):
    socks = []
    for _ in range(n):
        s = socket.create_connection((host, port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        socks.append(s)
    return socks


def _read(socks, got, done, deadline):
    """Take the replies off every socket (one thread, one selector) and
    hand each to ``got(sock, now, rid, status, scores, slots)`` until
    ``done()`` or the deadline."""
    sel = selectors.DefaultSelector()
    bufs = {}
    for s in socks:
        sel.register(s, selectors.EVENT_READ)
        bufs[s] = bytearray()
    while not done() and time.monotonic() < deadline:
        for key, _ in sel.select(1.0):
            try:
                chunk = key.fileobj.recv(1 << 16)
            except OSError:
                chunk = b""
            if not chunk:
                sel.unregister(key.fileobj)
                continue
            now = time.monotonic()
            buf = bufs[key.fileobj]
            buf += chunk
            for r in wire.parse_replies(buf):
                got(key.fileobj, now, *r)
    sel.close()


def open_loop(socks, sched, frames, t0, rec, id_base, deadline):
    """Send every request (its frame built beforehand) at ``t0 +
    sched['t']`` on its connection from one sender thread, while one
    reader thread takes the replies, so a slow reply never delays a
    send; record into ``rec``."""
    n = len(frames)
    rec.sched[:] = t0 + sched["t"]
    conn = sched["conn"]

    def send():
        for i in np.argsort(rec.sched, kind="stable"):
            wait = rec.sched[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            socks[conn[i]].sendall(frames[i])
            rec.sent[i] = time.monotonic()

    left = [n]

    def got(sock, now, rid, status, scores, slots):
        rec.reply(rid - id_base, now, status, scores, slots)
        left[0] -= 1

    reader = threading.Thread(target=_read, args=(
        socks, got, lambda: left[0] == 0, deadline))
    reader.start()
    send()
    reader.join()


def closed_loop(socks, streams, t0, t_end, pool, tenants, max_k, deadline):
    """Each client sends its next request when its last reply lands, from
    ``t0`` until ``t_end`` (one thread drives every connection); returns
    (Record, ctx, k, tenant)."""
    rows = []
    live = {}                            # socket -> (row, request id)
    client = {s: c for c, s in enumerate(socks)}
    count = [0] * len(socks)

    def send(sock):
        c = client[sock]
        ctx, k, tn = streams[c].next()
        count[c] += 1
        rid = (c << 24) + count[c]
        row = [time.monotonic(), np.nan, -1, ctx, k, tn, None, None]
        sock.sendall(wire.rank_frame(rid, pool[ctx], int(k), tenants[tn]))
        rows.append(row)
        live[sock] = (row, rid)

    def got(sock, now, rid, status, scores, slots):
        row, want = live.pop(sock)
        if rid != want:
            raise RuntimeError(f"reply {rid} where {want} was due")
        row[1:3], row[6:8] = (now, status), (scores, slots)
        if now < t_end:
            send(sock)

    wait = t0 - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    for sock in socks:
        send(sock)
    # every reply sends its client's next request until t_end, so the
    # loop ends when the last client's last reply is in
    _read(socks, got, lambda: not live, deadline)
    rec = Record(len(rows), max_k)
    for i, (sent, recv, status, ctx, k, tn, sc, sl) in enumerate(rows):
        rec.sched[i] = rec.sent[i] = sent
        if status >= 0:
            rec.reply(i, recv, status, sc, sl)
    meta = np.array([r[3:6] for r in rows], np.int64).reshape(-1, 3)
    return rec, meta[:, 0], meta[:, 1], meta[:, 2]


def phase(cfg, socks, pool, tenants, seconds, stream):
    """Prepare one stretch of the mix's traffic (an open loop's frames
    are built here, before its clock starts); returns ``go(t0)``, which
    runs it from ``t0`` and returns the record and the per-request
    ``(ctx, k, tenant)``."""
    mix = cfg["mix"]
    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, cfg["seed"], seconds, stream,
                                      len(pool), len(tenants))
        base = 1 + (stream << 26)
        frames = [wire.rank_frame(base + i, pool[c], int(k), tenants[tn])
                  for i, (c, k, tn) in enumerate(zip(
                      sched["ctx"], sched["k"], sched["tenant"]))]
        rec = Record(len(frames), cfg["max_k"])

        def go(t0):
            open_loop(socks, sched, frames, t0, rec, base,
                      t0 + seconds + GRACE_S)
            return rec, sched["ctx"], sched["k"], sched["tenant"]
        return go
    streams = [traffic.ClientStream(mix, cfg["seed"], (stream << 16) + c,
                                    len(pool), len(tenants))
               for c in range(len(socks))]
    return lambda t0: closed_loop(socks, streams, t0, t0 + seconds, pool,
                                  tenants, cfg["max_k"],
                                  t0 + seconds + GRACE_S)


def main() -> int:
    cfg = json.loads(sys.stdin.buffer.readline())
    pool = np.frombuffer(sys.stdin.buffer.read(cfg["pool_bytes"]),
                         np.int32).reshape(cfg["pool"], cfg["n_ctx"])
    tenants = cfg["tenants"]
    mix = cfg["mix"]
    n_socks = int(mix["connections"] if mix["loop"] == "open"
                  else mix["clients"])
    socks = _connect(cfg["host"], cfg["port"], n_socks)
    out = sys.stdout.buffer
    if cfg["warmup_s"] > 0:
        phase(cfg, socks, pool, tenants, cfg["warmup_s"],
              traffic.WARMUP)(time.monotonic() + 0.05)
    window = phase(cfg, socks, pool, tenants, cfg["seconds"],
                   traffic.SCHEDULE)
    # the generator is not what is measured: no collector pause in it
    gc.collect()
    gc.disable()
    out.write(b"READY\n")
    out.flush()
    line = sys.stdin.buffer.readline().split()
    if not line or line[0] != b"GO":
        return 1
    rec, ctx, k, tenant = window(float(line[1]))
    for s in socks:
        s.close()
    blob = io.BytesIO()
    np.savez(blob, ctx=ctx, k=k, tenant=tenant,
             **{f: getattr(rec, f) for f in ("sched", "sent", "recv",
                                             "status", "served", "scores",
                                             "slots")})
    data = blob.getvalue()
    out.write(b"RESULT %d\n" % len(data))
    out.write(data)
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
