"""The load generator: one process, one thread, plain sockets.

Runs as a child of the harness and touches neither JAX nor the program, so
the server's threads do not share an interpreter lock with it. It reads a
plan (``benchmark/traffic.py``) as JSON on standard input, drives
``POST /v1/generate`` on the front door, reads the SSE streams with
non-blocking sockets, stamps every token with the monotonic clock as it
arrives, and writes JSON lines to standard output: ``open`` and ``close``
when the measured window opens and closes, then ``result``.

The raw-socket exchange is ``chip_smoke.py``'s ``_http``/``_generate``
(PR 21), made non-blocking. Times are ``time.monotonic()`` seconds, which
the parent shares.

open    every request is sent when it is due (``due`` seconds from the
        window's opening, negative in the lead-in), whatever the server
        does; the run ends when all of them have ended.
closed  ``clients`` callers each send the next request of the plan when
        their last one ended; the window opens when ``open_when_streaming``
        streams are receiving tokens, and at its close the streams still
        running are cut.
A traced run's plan has a ``tail_s``: the load goes on that long past the
window's close (arrivals at the rate, or the callers), so that the
profiler's capture, which stalls the server when it stops, is taken in the
same steady state and costs the window nothing.
"""
from __future__ import annotations

import importlib.util
import json
import os
import selectors
import socket
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
# run as a script: keep this directory's module names out of the path, and
# load the one generator module it shares with the harness by its file
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
_spec = importlib.util.spec_from_file_location(
    "benchmark_traffic", os.path.join(HERE, "traffic.py"))
_traffic = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_traffic)
prompt_tokens = _traffic.prompt_tokens


class Stream:
    def __init__(self, idx: int, req: Dict):
        self.idx, self.req = idx, req
        self.sock: Optional[socket.socket] = None
        self.buf = b""
        self.head_done = False
        self.status = 0
        self.t_sent = 0.0
        self.t_tokens: List[float] = []
        self.tokens: List[int] = []
        self.terminal: Optional[Dict] = None
        self.ended = False
        self.cut = False

    def feed(self, data: bytes, now: float) -> None:
        self.buf += data
        if not self.head_done:
            head, sep, rest = self.buf.partition(b"\r\n\r\n")
            if not sep:
                return
            self.status = int(head.split()[1])
            self.head_done, self.buf = True, rest
        while True:
            frame, sep, rest = self.buf.partition(b"\n\n")
            if not sep:
                return
            self.buf = rest
            if frame.startswith(b"data: "):
                doc = json.loads(frame[6:])
                if doc.get("done"):
                    self.terminal = doc
                elif "token" in doc:
                    self.tokens.append(doc["token"])
                    self.t_tokens.append(now)

    def record(self, t_open: float) -> Dict:
        term = self.terminal or {}
        return {"idx": self.idx, "tag": self.req["tag"],
                "prompt_len": self.req["prompt_len"],
                "max_new": self.req["max_new"],
                "due": self.req.get("due"),
                "in_window": self.req.get("in_window", True),
                "sent": self.t_sent - t_open, "status": self.status,
                "t_tokens": [t - t_open for t in self.t_tokens],
                "tokens": self.tokens, "cut": self.cut,
                "reason": term.get("reason"),
                "request_id": term.get("request_id"),
                "terminal_tokens": term.get("tokens")}


def emit(doc: Dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def run(plan: Dict) -> None:
    port, seed, vocab = plan["port"], plan["seed"], plan["vocab"]
    seconds = float(plan["seconds"])
    reqs = plan["requests"]
    closed = plan["mode"] == "closed"
    sel = selectors.DefaultSelector()
    streams: List[Stream] = []
    live = 0

    def send(i: int, now: float) -> None:
        nonlocal live
        st = Stream(i, reqs[i])
        body = json.dumps({
            "prompt": prompt_tokens(seed, reqs[i]["tag"],
                                    reqs[i]["prompt_len"], vocab),
            "max_new_tokens": reqs[i]["max_new"],
            "temperature": plan.get("temperature", 0.0)}).encode()
        head = (f"POST /v1/generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        st.sock = socket.create_connection(("127.0.0.1", port), timeout=30)
        st.sock.sendall(head + body)
        st.t_sent = time.monotonic()
        st.sock.setblocking(False)
        sel.register(st.sock, selectors.EVENT_READ, st)
        streams.append(st)
        live += 1

    def end(st: Stream, cut: bool = False) -> None:
        nonlocal live
        if st.ended:
            return
        st.ended, st.cut = True, cut
        sel.unregister(st.sock)
        st.sock.close()
        live -= 1

    nxt = 0
    if closed:
        t_open = None
        for _ in range(min(plan["clients"], len(reqs))):
            send(nxt, time.monotonic())
            nxt += 1
    else:
        t_open = float(plan["t_open"])
        emitted_open = False
    t_close = None if t_open is None else t_open + seconds
    closed_out = cut_done = False
    tail_s = float(plan.get("tail_s", 0.0))
    while True:
        now = time.monotonic()
        if not closed:
            if not emitted_open and now >= t_open:
                emit({"event": "open", "t": t_open})
                emitted_open = True
            while nxt < len(reqs) and now >= t_open + reqs[nxt]["due"]:
                send(nxt, now)
                nxt += 1
                now = time.monotonic()
        if t_close is not None and now >= t_close and not closed_out:
            emit({"event": "close", "t": t_close})
            closed_out = True
        if closed and closed_out and not cut_done and \
                now >= t_close + tail_s:
            cut_done = True
            for st in streams:
                end(st, cut=True)
        if closed_out and live == 0 and (cut_done or nxt >= len(reqs)):
            break
        wait = 0.05
        if not closed and nxt < len(reqs):
            wait = min(wait, max(0.0, t_open + reqs[nxt]["due"] - now))
        if t_close is not None and not closed_out:
            wait = min(wait, max(0.0, t_close - now))
        for key, _ in sel.select(wait):
            st = key.data
            try:
                data = st.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError:
                data = b""
            now = time.monotonic()
            if data:
                st.feed(data, now)
            if not data or st.terminal is not None:
                end(st)
                if closed and not cut_done and nxt < len(reqs):
                    send(nxt, now)
                    nxt += 1
        if closed and t_open is None:
            streaming = sum(1 for st in streams
                            if not st.ended and st.t_tokens)
            if streaming >= plan["open_when_streaming"]:
                t_open = time.monotonic()
                t_close = t_open + seconds
                emit({"event": "open", "t": t_open})
    emit({"event": "result", "t_open": t_open, "seconds": seconds,
          "unsent": len(reqs) - nxt if not closed else 0,
          "streams": [st.record(t_open) for st in streams]})


if __name__ == "__main__":
    run(json.load(sys.stdin))
