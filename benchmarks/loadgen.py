"""The load generator: one thread, raw sockets, one selector.

A thread per request adds scheduler jitter that rivals the latencies
being measured on a small host, so every connection lives in one event
loop. (The idea is ``infer/bench_serve.py::_client_wave``'s; this copy
adds a due-time schedule, per-token stamps and incremental parsing, and
lives here so that the program cannot change how it is timed.)

Clocks: ``time.monotonic`` throughout. An open-loop request is timed
from the moment it was DUE, so a stall that delays later sends is
charged to them; how late each send ran is recorded beside it.

No call in the loop blocks: a connection is opened and its request
handed over through the selector, so a listener that accepts or reads
late delays that one request's ``sent`` and no other request's stamps.
Between events the loop waits in ``select`` until the next due time.
``poll=True`` makes it spin instead (one core, for the length of a
run): for a cell whose arrivals follow quiet spells in which server and
generator both sleep, where the chip's machines woke the waiting loop
late by up to seconds (``PERF.md`` section 6, PR 37). Such a cell's
file says so (``generator_polls``); no other cell pays the core.
A caller that measures wraps its run in ``collector_off()`` BEFORE it
fixes the due times: the garbage collector is then frozen and off for
the length of the run (the server's process does the same after its
warm grid). ``run`` reports
the loop's own worst moments — the longest call of ``send``, the longest
turn between two waits, the latest a request was taken up, the longest
hand-over — so that a late generator can be told from a slow server.

Standard library only.
"""

from __future__ import annotations

import contextlib
import errno
import gc
import heapq
import http.client
import json
import os
import selectors
import socket
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple


class Record:
    """What happened to one request."""

    __slots__ = ("request", "due", "started", "sent", "first", "done",
                 "stamps", "tokens", "status", "error", "end", "_sock",
                 "_buf", "_hdr", "_out")

    def __init__(self, request: Dict[str, Any], due: float):
        self.request = request
        self.due = due
        self.started: Optional[float] = None  # the loop took it up
        self.sent: Optional[float] = None     # its last byte handed over
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
        self._out: Optional[memoryview] = None

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


CONNECT_TIMEOUT_S = 10.0


@contextlib.contextmanager
def collector_off():
    """A full collection inside a run is a stall of the one thread that
    stamps every token: collect now, keep what is alive out of later
    passes, and leave the collector off until the block ends. Entered
    before the due times are fixed, because the collection itself takes
    its time in a process with a large heap."""
    was_on = gc.isenabled()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.unfreeze()
        if was_on:
            gc.enable()


def run(host: str, port: int,
        schedule: Iterable[Tuple[float, Dict[str, Any]]],
        timers: Iterable[Tuple[float, Callable[[], None]]] = (),
        idle_timeout: float = 120.0,
        loop_info: Optional[Dict[str, float]] = None,
        poll: bool = False) -> List[Record]:
    """Send each ``(due, request)`` at its due time (monotonic seconds).

    Returns every record, finished or not. The loop ends when nothing is
    scheduled or in flight, or when nothing at all has arrived for
    ``idle_timeout`` seconds. ``timers`` are ``(when, callback)`` pairs
    run from the loop at their time. ``poll``: spin between events
    rather than wait for the next one. ``loop_info``, where given, is
    filled with the loop's own worst moments in ms: ``send_call_max``
    (one call of ``send``), ``turn_max`` (the work between two waits),
    ``taken_up_late_max`` (a request's ``started`` after its due time)
    and ``handover_max`` (``started`` to ``sent``: connect, accept queue
    and the server's socket buffer)."""
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
    writing: set = set()                  # records not yet handed over
    live = 0
    last_io = time.monotonic()
    worst = {"send_call_max": 0.0, "turn_max": 0.0}

    def fail(rec: Record, why: str) -> None:
        nonlocal live
        if rec in writing:                # registered, counted as live
            sel.unregister(rec._sock)
            writing.discard(rec)
            live -= 1
        if rec._sock is not None:
            rec._sock.close()
            rec._sock = None
        rec._out = None
        rec.error = f"connect/send failed: {why}"
        rec.status = 0
        rec.end = time.monotonic()

    def send(req: Dict[str, Any], due: float) -> None:
        """Open the connection without waiting for it; the request goes
        out from the loop once the socket is writable."""
        nonlocal live
        rec = Record(req, due)
        records.append(rec)
        rec.started = time.monotonic()
        body = req["body"]
        head = (f"POST /generate HTTP/1.1\r\nHost: {host}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        rec._out = memoryview(head + body)
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            rec._sock = s
            s.setblocking(False)
            err = s.connect_ex((host, port))
        except OSError as e:
            fail(rec, str(e))
            return
        if err not in (0, errno.EINPROGRESS):
            fail(rec, os.strerror(err))
            return
        sel.register(s, selectors.EVENT_WRITE, rec)
        writing.add(rec)
        live += 1

    def hand_over(rec: Record) -> None:
        """The socket is writable: connected (or refused); send what it
        takes, and turn to reading once the last byte has gone."""
        err = rec._sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            fail(rec, os.strerror(err))
            return
        try:
            n = rec._sock.send(rec._out)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            fail(rec, str(e))
            return
        rec._out = rec._out[n:]
        if not len(rec._out):
            rec._out = None
            rec.sent = time.monotonic()
            writing.discard(rec)
            sel.modify(rec._sock, selectors.EVENT_READ, rec)

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
        woke = time.monotonic()
        while heap or live:
            now = time.monotonic()
            while heap and heap[0][0] <= now:
                due, _, kind, payload = heapq.heappop(heap)
                if kind == "send":
                    send(payload, due)
                    worst["send_call_max"] = max(
                        worst["send_call_max"],
                        time.monotonic() - records[-1].started)
                else:
                    payload()
                now = time.monotonic()
            for rec in [r for r in writing
                        if now - r.started > CONNECT_TIMEOUT_S]:
                fail(rec, "timed out")
            worst["turn_max"] = max(worst["turn_max"],
                                    time.monotonic() - woke)
            wait = 0.0
            if not poll:
                wait = 0.5
                if heap:
                    wait = min(wait, max(heap[0][0] - time.monotonic(), 0.0))
            events = sel.select(timeout=wait) if live else []
            if not live and wait > 0:
                time.sleep(wait)
            woke = time.monotonic()
            for key, mask in events:
                rec = key.data
                if mask & selectors.EVENT_WRITE:
                    hand_over(rec)
                    last_io = time.monotonic()
                    continue
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
    if loop_info is not None:
        worst["taken_up_late_max"] = max(
            (r.started - r.due for r in records), default=0.0)
        worst["handover_max"] = max(
            (r.sent - r.started for r in records if r.sent is not None),
            default=0.0)
        loop_info.update({k: v * 1e3 for k, v in worst.items()})
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
