"""The load generator: one thread, raw sockets, one selector.

A thread per request adds scheduler jitter that rivals the latencies
being measured on a small host, so every connection lives in one event
loop. (The idea is ``infer/bench_serve.py::_client_wave``'s; this copy
adds a due-time schedule, per-token stamps and incremental parsing, and
lives here so that the program cannot change how it is timed.)

Clocks: ``time.monotonic`` throughout. An open-loop request is timed
from the moment it was DUE, so a stall that delays later sends is
charged to them; how late each send ran is recorded beside it.

Standard library only.
"""

from __future__ import annotations

import heapq
import http.client
import json
import selectors
import socket
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple


class Record:
    """What happened to one request."""

    __slots__ = ("request", "due", "sent", "first", "done", "stamps",
                 "tokens", "status", "error", "end", "_sock", "_buf",
                 "_hdr")

    def __init__(self, request: Dict[str, Any], due: float):
        self.request = request
        self.due = due
        self.sent: Optional[float] = None
        self.first: Optional[float] = None
        self.done: Optional[float] = None     # the ``done`` line came
        self.end: Optional[float] = None      # ended, well or not
        self.stamps: List[float] = []
        self.tokens: List[int] = []
        self.status: Optional[int] = None
        self.error: Optional[str] = None
        self._sock: Optional[socket.socket] = None
        self._buf = b""
        self._hdr = False

    @property
    def ok(self) -> bool:
        """200, ended with a ``done`` line, every token asked for came
        and none is out of the vocabulary (checked by the caller)."""
        return (self.status == 200 and self.error is None
                and self.done is not None
                and len(self.tokens) == self.request["max_new"])


def _feed(rec: Record, piece: bytes, now: float) -> bool:
    """Parse what arrived; return True when the response has ended."""
    rec._buf += piece
    if not rec._hdr:
        pos = rec._buf.find(b"\r\n\r\n")
        if pos < 0:
            return False
        head = rec._buf[:pos]
        rec._buf = rec._buf[pos + 4:]
        rec._hdr = True
        try:
            rec.status = int(head.split(b" ", 2)[1])
        except (IndexError, ValueError):
            rec.status = 0
        if rec.status != 200:
            rec.error = head.split(b"\r\n", 1)[0].decode("latin1")
            return True
    ended = False
    while True:
        nl = rec._buf.find(b"\n")
        if nl < 0:
            break
        line, rec._buf = rec._buf[:nl].strip(), rec._buf[nl + 1:]
        if line == b"0":
            return True           # the terminal chunk: the stream is over
        if not line.startswith(b"{"):
            continue              # chunk-size framing
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if not isinstance(obj, dict):
            continue
        toks = obj.get("tokens") or []
        if toks:
            if rec.first is None:
                rec.first = now
            rec.tokens.extend(int(t) for t in toks)
            rec.stamps.extend([now] * len(toks))
        if obj.get("error"):
            rec.error = str(obj["error"])[:300]
            ended = True
        if obj.get("done"):
            rec.done = now
    return ended


def run(host: str, port: int,
        schedule: Iterable[Tuple[float, Dict[str, Any]]],
        timers: Iterable[Tuple[float, Callable[[], None]]] = (),
        idle_timeout: float = 120.0) -> List[Record]:
    """Send each ``(due, request)`` at its due time (monotonic seconds).

    Returns every record, finished or not. The loop ends when nothing is
    scheduled or in flight, or when nothing at all has arrived for
    ``idle_timeout`` seconds. ``timers`` are ``(when, callback)`` pairs
    run from the loop at their time."""
    sel = selectors.DefaultSelector()
    seq = 0
    heap: List[Tuple[float, int, str, Any]] = []
    for due, req in schedule:
        heapq.heappush(heap, (due, seq, "send", req))
        seq += 1
    for when, fn in timers:
        heapq.heappush(heap, (when, seq, "timer", fn))
        seq += 1
    records: List[Record] = []
    live = 0
    last_io = time.monotonic()

    def send(req: Dict[str, Any], due: float) -> None:
        nonlocal live
        rec = Record(req, due)
        records.append(rec)
        body = req["body"]
        head = (f"POST /generate HTTP/1.1\r\nHost: {host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        try:
            s = socket.create_connection((host, port), timeout=10.0)
            s.sendall(head + body)
        except OSError as e:
            rec.error = f"connect/send failed: {e}"
            rec.status = 0
            rec.end = time.monotonic()
            return
        rec.sent = time.monotonic()
        s.setblocking(False)
        rec._sock = s
        sel.register(s, selectors.EVENT_READ, rec)
        live += 1

    def finish(rec: Record, now: float, ended: bool) -> None:
        nonlocal live
        sel.unregister(rec._sock)
        rec._sock.close()
        rec._sock = None
        live -= 1
        rec.end = now
        if rec.error is None and (rec.status != 200 or rec.done is None
                                  or not ended):
            rec.error = "connection closed before the stream ended"

    try:
        while heap or live:
            now = time.monotonic()
            while heap and heap[0][0] <= now:
                due, _, kind, payload = heapq.heappop(heap)
                if kind == "send":
                    send(payload, due)
                else:
                    payload()
                now = time.monotonic()
            wait = 0.5
            if heap:
                wait = min(wait, max(heap[0][0] - now, 0.0))
            events = sel.select(timeout=wait) if live else []
            if not live and wait > 0:
                time.sleep(wait)
            for key, _ in events:
                rec = key.data
                try:
                    piece = rec._sock.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError as e:
                    rec.error = f"recv failed: {e}"
                    finish(rec, time.monotonic(), False)
                    continue
                now = time.monotonic()
                last_io = now
                if not piece:
                    finish(rec, now, False)
                elif _feed(rec, piece, now):
                    finish(rec, now, True)
            if live and time.monotonic() - last_io > idle_timeout:
                break
    finally:
        for rec in records:
            if rec._sock is not None:
                try:
                    sel.unregister(rec._sock)
                except (KeyError, ValueError):
                    pass
                rec._sock.close()
                rec._sock = None
        sel.close()
    return records


def http_get(host: str, port: int, path: str, timeout: float = 10.0
             ) -> Tuple[int, bytes]:
    """A blocking GET (health, /metrics): outside the timed loop only."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def parse_prometheus(text: str) -> Dict[str, float]:
    """Sum of every sample of each metric name (labels dropped): enough
    for counters, gauges and a histogram's ``_sum`` / ``_count``."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            name_part, value = line.rsplit(" ", 1)
            v = float(value)
        except ValueError:
            continue
        name = name_part.split("{", 1)[0]
        out[name] = out.get(name, 0.0) + v
    return out
