#!/usr/bin/env python3
"""The load generator: a process of its own that speaks HTTP/1.1 over
plain sockets to the door and imports nothing of the program.  One
thread, one selector; timestamps are time.monotonic().

    python3 benchmark/lib/loadgen.py <spec.json>

Commands on stdin, answers on stdout, one line each:
  start <port>     connect to the door's port; first the warm-up's ladder of bursts (`warm_bursts`: n
                   reviews pipelined on one connection, all awaited,
                   for each n: the micro-batcher pads batches to row
                   buckets and each bucket is an executable of its
                   own, so the warm-up meets every bucket the window
                   can), then begin offering load (kind "closed": `connections` x
                   `inflight_per_connection` reviews in flight at all
                   times; kind "open": arrivals at `rate_per_s` on a
                   schedule drawn from the seed, request k on connection
                   k mod `connections`, pipelined) -> "warmed" once
                   `warm_reviews` answers are in; load goes on
  open <seconds>   the window opens now -> "closed" once it has closed
                   and every review sent has been answered (or
                   `timeout_s` past the close has gone); results are in
                   the spec's `out` file

Every seed offers the same work in another order: the same multiset of
inter-arrival gaps (an exponential sample of a fixed stream) and the
same number of violating reviews, both shuffled by the seed.
"""

from __future__ import annotations

import json
import os
import random
import selectors
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import corpus  # noqa: E402

HEAD = (b"POST /v1/admit HTTP/1.1\r\nHost: door\r\n"
        b"Content-Type: application/json\r\nContent-Length: ")


def build_bodies(spec: dict) -> list:
    """n request bodies, unique in name and uid, from a pool of Pod
    shapes: exactly round(share x n) violating, placed by the seed."""
    n, seed, tag = spec["bodies"], spec["seed"], spec["tag"]
    pool = corpus.review_pods(
        2048, seed, 0.5, "@@NAME@@")  # half good, half bad shapes
    good, bad = [], []
    for p in pool:
        p["metadata"]["name"] = "@@NAME@@"
        (good if corpus._compliant(p) else bad).append(
            corpus.admission_body(p, "@@UID@@"))
    rng = random.Random(corpus.seed32(seed, 5))
    n_bad = round(n * spec["violating_share"])
    flags = [True] * n_bad + [False] * (n - n_bad)
    rng.shuffle(flags)
    out = []
    for i, is_bad in enumerate(flags):
        src = bad if is_bad else good
        t = src[rng.randrange(len(src))]
        out.append(t.replace(b"@@NAME@@", f"{tag}-{i}".encode())
                    .replace(b"@@UID@@", f"{tag}-u{i}".encode()))
    return out


def arrivals(spec: dict, n: int) -> list:
    """n due times (seconds from start): the same exponential gaps for
    every seed, in an order the seed picks."""
    fixed = random.Random(20260930)
    gaps = [fixed.expovariate(spec["rate_per_s"]) for _ in range(n)]
    random.Random(corpus.seed32(spec["seed"], 6)).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


class Conn:
    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.inbuf = bytearray()
        self.outbuf = bytearray()
        self.pending = []   # request indices awaiting answers, in order
        self.need = None    # (status, body length) once headers are in


class Generator:
    def __init__(self, spec: dict):
        self.spec = spec
        self.bodies = build_bodies(spec)
        self.closed_loop = spec["kind"] == "closed"
        n = len(self.bodies)
        # open loop: due times of the bodies after the warm-up's bursts
        # (those are due when sent), from the start of the offered load
        self.first_paced = sum(spec.get("warm_bursts", ()))
        self.due = None if self.closed_loop else (
            [0.0] * self.first_paced
            + arrivals(spec, n - self.first_paced))
        self.sent = [0.0] * n
        self.done = [0.0] * n
        self.status = [0] * n
        self.answer = [None] * n
        self.next = 0
        self.answered = 0
        self.sel = selectors.DefaultSelector()
        self.conns = []
        self.t_open = self.t_close = None
        self.stop_sending = False
        self.max_gap = 0.0

    def connect(self, port: int):
        for _ in range(self.spec["connections"]):
            c = Conn(port)
            self.conns.append(c)
            self.sel.register(c.sock, selectors.EVENT_READ, c)

    def send(self, c: Conn, now: float) -> bool:
        i = self.next
        if i >= len(self.bodies) or self.stop_sending:
            return False
        self.next += 1
        b = self.bodies[i]
        self.sent[i] = now
        c.pending.append(i)
        c.outbuf += HEAD + str(len(b)).encode() + b"\r\n\r\n" + b
        return True

    def flush(self, c: Conn):
        if not c.outbuf:
            return
        try:
            k = c.sock.send(c.outbuf)
            del c.outbuf[:k]
        except (BlockingIOError, InterruptedError):
            pass
        want = selectors.EVENT_READ | (
            selectors.EVENT_WRITE if c.outbuf else 0)
        self.sel.modify(c.sock, want, c)

    def on_read(self, c: Conn):
        try:
            data = c.sock.recv(1 << 18)
        except (BlockingIOError, InterruptedError):
            return
        if not data:
            raise ConnectionError("the door closed a connection")
        now = time.monotonic()
        c.inbuf += data
        while True:
            if c.need is None:
                end = c.inbuf.find(b"\r\n\r\n")
                if end < 0:
                    return
                head = bytes(c.inbuf[:end]).split(b"\r\n")
                status = int(head[0].split()[1])
                length = 0
                for line in head[1:]:
                    k, _, v = line.partition(b":")
                    if k.strip().lower() == b"content-length":
                        length = int(v)
                del c.inbuf[:end + 4]
                c.need = (status, length)
            status, length = c.need
            if len(c.inbuf) < length:
                return
            i = c.pending.pop(0)
            self.status[i] = status
            self.answer[i] = bytes(c.inbuf[:length])
            self.done[i] = now
            del c.inbuf[:length]
            c.need = None
            self.answered += 1
            if self.closed_loop and self.send(c, now):
                self.flush(c)

    def burst(self, n: int):
        """n reviews pipelined on the first connection, all awaited."""
        c, want = self.conns[0], self.answered + n
        now = time.monotonic()
        was, self.closed_loop = self.closed_loop, False
        for _ in range(n):
            self.send(c, now)
        self.flush(c)
        deadline = now + 120
        while self.answered < want and time.monotonic() < deadline:
            self.step(0.05)
        self.closed_loop = was

    def step(self, timeout: float):
        for key, ev in self.sel.select(timeout):
            c = key.data
            if ev & selectors.EVENT_READ:
                self.on_read(c)
            if ev & selectors.EVENT_WRITE:
                self.flush(c)

    def run_until(self, t0: float, cond):
        """Offer load until cond() holds."""
        last = time.monotonic()
        while not cond():
            now = time.monotonic()
            # a loop turn never waits more than 50 ms: a longer gap is
            # this process held up (by the host, not by the door)
            self.max_gap = max(self.max_gap, now - last)
            last = now
            if self.t_close is not None and now >= self.t_close and (
                    self.closed_loop or self.next >= len(self.bodies)
                    or t0 + self.due[self.next] >= self.t_close):
                self.stop_sending = True
            if self.closed_loop or self.stop_sending \
                    or self.next >= len(self.bodies):
                self.step(0.05)
                continue
            due = t0 + self.due[self.next]
            if now >= due:
                c = self.conns[self.next % len(self.conns)]
                self.send(c, now)
                self.flush(c)
                self.step(0)
            else:
                self.step(min(due - now, 0.05))

    def results(self, t0: float) -> dict:
        n = self.next
        rows = []
        for i in range(n):
            paced = self.due and i >= self.first_paced
            due = (t0 + self.due[i]) if paced else self.sent[i]
            rows.append((i, due, self.sent[i], self.done[i], self.status[i]))
        return {"t_open": self.t_open, "t_close": self.t_close,
                "sent": n, "answered": self.answered, "rows": rows,
                "bodies_left": len(self.bodies) - n,
                "max_gap_ms": self.max_gap * 1e3}


def main(argv) -> int:
    spec = json.load(open(argv[1]))
    gen = Generator(spec)
    print("built", flush=True)
    t0 = None
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "start":
            gen.connect(int(cmd[1]))
            for n in spec.get("warm_bursts", ()):
                gen.burst(n)
            t0 = time.monotonic()
            if gen.closed_loop:
                for c in gen.conns:
                    for _ in range(spec["inflight_per_connection"]):
                        gen.send(c, t0)
                    gen.flush(c)
            gen.run_until(t0, lambda: gen.answered >= spec["warm_reviews"])
            print("warmed", flush=True)
        elif cmd[0] == "open":
            seconds = float(cmd[1])
            gen.t_open = time.monotonic()
            gen.max_gap = 0.0
            gen.t_close = gen.t_open + seconds
            give_up = gen.t_close + spec["timeout_s"]
            gen.run_until(t0, lambda: (
                gen.stop_sending and (gen.answered >= gen.next
                                      or time.monotonic() > give_up)))
            res = gen.results(t0)
            with open(spec["out"] + ".answers", "wb") as f:
                for i in range(gen.next):
                    a = gen.answer[i] or b""
                    f.write(len(a).to_bytes(4, "big") + a)
            with open(spec["out"], "w") as f:
                json.dump(res, f)
            print("closed", flush=True)
            return 0
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
