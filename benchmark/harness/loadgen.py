"""The load generator: one process, one thread, no JAX.

    python3 benchmark/harness/loadgen.py <plan.json> <records.json>

It drives the HTTP proxy as clients do, streaming (SSE over chunked
transfer-encoding), with non-blocking sockets under one selector, and
stamps every token with the time it arrived. A closed loop keeps
`clients` requests outstanding, each client sending its next request
when its last one ends; an open loop sends each request when it is due,
late or not, and records how late. It runs until a line `stop <drain
seconds>` arrives on its standard input, then sends nothing new, waits
at most that long until every request in flight has its first token,
writes every request's record and exits. All times are `time.perf_counter()`, which on Linux is
the machine's monotonic clock and so the same in the parent.
"""

from __future__ import annotations

import json
import os
import selectors
import socket
import sys
import time

import numpy as np


class Request:
    def __init__(self, index, spec, due, plan):
        self.index = index
        self.due = due
        self.max_tokens = spec["max_tokens"]
        rng = np.random.default_rng([plan["seed"], index])
        ids = rng.integers(0, plan["vocab"], spec["prompt_len"]).tolist()
        body = json.dumps({"prompt_ids": ids, "max_tokens": self.max_tokens,
                           "stream": True, "temperature": 0.0}).encode()
        self.out = (f"POST {plan['route']} HTTP/1.1\r\nHost: bench\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(body)}\r\n"
                    f"Connection: close\r\n\r\n").encode() + body
        self.sent = None
        self.status = None
        self.buf = b""
        self.in_body = False
        self.chunked = False
        self.chunk_left = None  # bytes of the current chunk still to come
        self.event = b""
        self.arrivals = []
        self.tokens = []
        self.done = False
        self.error = None
        self.t_end = None
        self.sock = None

    def record(self):
        return {"index": self.index, "due": self.due, "sent": self.sent,
                "status": self.status, "max_tokens": self.max_tokens,
                "arrivals": self.arrivals, "tokens": self.tokens,
                "done": self.done, "error": self.error, "t_end": self.t_end}

    # -- parsing ----------------------------------------------------------

    def feed(self, data, now):
        """Bytes from the socket, stamped `now`."""
        self.buf += data
        if not self.in_body:
            head, sep, rest = self.buf.partition(b"\r\n\r\n")
            if not sep:
                return
            self.status = int(head.split(b" ", 2)[1])
            self.chunked = b"transfer-encoding: chunked" in head.lower()
            self.in_body = True
            self.buf = rest
        if self.chunked:
            self._dechunk(now)
        else:
            self._events(self.buf, now)
            self.buf = b""

    def _dechunk(self, now):
        while self.buf:
            if self.chunk_left is None:
                line, sep, rest = self.buf.partition(b"\r\n")
                if not sep:
                    return
                if not line:  # the CRLF that ends the previous chunk
                    self.buf = rest
                    continue
                self.chunk_left = int(line.split(b";")[0], 16)
                self.buf = rest
                if self.chunk_left == 0:
                    self.chunk_left = None
                    self.buf = b""
                    return
            take = self.buf[:self.chunk_left]
            self.buf = self.buf[len(take):]
            self.chunk_left -= len(take)
            if self.chunk_left == 0:
                self.chunk_left = None
            self._events(take, now)

    def _events(self, data, now):
        self.event += data
        while b"\n\n" in self.event:
            ev, _, self.event = self.event.partition(b"\n\n")
            if not ev.startswith(b"data: "):
                continue
            payload = ev[6:]
            if payload == b"[DONE]":
                self.done = True
                continue
            item = json.loads(payload)
            if "token" in item:
                self.tokens.append(item["token"])
                self.arrivals.append(now)
            else:
                self.error = str(item)[:200]


def run(plan, stop_file):
    sel = selectors.DefaultSelector()
    specs = plan["requests"]
    open_loop = plan["loop"] == "open"
    addr = (plan["host"], plan["port"])
    records, in_flight = [], set()
    next_index = 0
    t_start = time.perf_counter()
    stop_at = None  # when set: the deadline of the drain
    os.set_blocking(stop_file.fileno(), False)
    sel.register(stop_file, selectors.EVENT_READ, "stop")
    print(json.dumps({"started": t_start}), flush=True)

    def launch(due):
        nonlocal next_index
        req = Request(next_index, specs[next_index], due, plan)
        next_index += 1
        req.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        req.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        req.sock.setblocking(False)
        req.sock.connect_ex(addr)
        sel.register(req.sock, selectors.EVENT_WRITE, req)
        in_flight.add(req)

    def finish(req, now, error=None):
        if error and not req.done:
            req.error = req.error or error
        req.t_end = now
        sel.unregister(req.sock)
        req.sock.close()
        in_flight.discard(req)
        records.append(req.record())
        # A closed loop's client sends its next request at once.
        if not open_loop and stop_at is None and next_index < len(specs):
            launch(now)

    if not open_loop:
        for _ in range(min(plan["clients"], len(specs))):
            launch(time.perf_counter())

    while True:
        now = time.perf_counter()
        if stop_at is not None and (
                now >= stop_at or all(r.arrivals for r in in_flight)):
            break  # every request that was sent has its first token
        timeout = 0.05
        if open_loop and stop_at is None and next_index < len(specs):
            due = t_start + specs[next_index]["due_s"]
            if due <= now:
                launch(due)
                continue
            timeout = min(timeout, due - now)
        for key, mask in sel.select(timeout):
            now = time.perf_counter()
            req = key.data
            if req == "stop":
                # `stop <drain seconds>`, or the parent is gone (EOF)
                words = os.read(stop_file.fileno(), 4096).decode().split()
                stop_at = now + (float(words[1]) if len(words) > 1 else 0.0)
                sel.unregister(stop_file)
                continue
            try:
                if mask & selectors.EVENT_WRITE:
                    if req.sent is None:
                        req.sent = now
                    n = req.sock.send(req.out)
                    req.out = req.out[n:]
                    if not req.out:
                        sel.modify(req.sock, selectors.EVENT_READ, req)
                    continue
                data = req.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                finish(req, now, f"{type(e).__name__}: {e}")
                continue
            if data:
                req.feed(data, now)
                if not req.done:
                    continue
            finish(req, now, None if req.done else "closed before [DONE]")
    now = time.perf_counter()
    for req in list(in_flight):  # cut off by the end of the run
        req.t_end = now
        req.sock.close()
        records.append(req.record())
    return {"t_start": t_start, "t_stop": now, "offered": next_index,
            "exhausted": next_index >= len(specs), "records": records}


def main(argv):
    plan_path, out_path = argv[1:3]
    with open(plan_path) as f:
        plan = json.load(f)
    result = run(plan, sys.stdin)
    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main(sys.argv)
