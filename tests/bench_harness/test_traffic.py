"""The traffic generators and the load generator: determinism per seed,
the same set of work for every seed, due-time timing and per-token
stamps against a scripted server."""

import http.server
import json
import socket
import threading
import time

import numpy as np
import pytest

from benchmarks import loadgen, manifest
from benchmarks.traffic import batches, requests

CHAT = {"generator": "requests", "rate_rps": 5.0,
        "arrivals": {"process": "poisson"},
        "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                          "min": 32, "max": 1024},
        "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                          "min": 8, "max": 256},
        "lead_in_s": 4, "shape_seed": 7}


def _shape(plan):
    return sorted((len(r["prompt"]), r["max_new"]) for r in plan["requests"])


def test_same_seed_same_requests():
    a = requests.generate(CHAT, 2 ** 31 + 5, 30, 32768, 1280)
    b = requests.generate(CHAT, 2 ** 31 + 5, 30, 32768, 1280)
    assert [r["body"] for r in a["requests"]] == \
        [r["body"] for r in b["requests"]]
    assert [r["due_s"] for r in a["requests"]] == \
        [r["due_s"] for r in b["requests"]]


def test_every_seed_replays_one_schedule_with_other_ids():
    a = requests.generate(CHAT, 1, 30, 32768, 1280)
    b = requests.generate(CHAT, 2 ** 32 + 9, 30, 32768, 1280)
    assert [(len(r["prompt"]), r["max_new"], r["due_s"])
            for r in a["requests"]] == \
        [(len(r["prompt"]), r["max_new"], r["due_s"])
         for r in b["requests"]]
    assert a["requests"][0]["prompt"] != b["requests"][0]["prompt"]
    other = requests.generate(dict(CHAT, shape_seed=8), 1, 30, 32768, 1280)
    assert _shape(other) != _shape(a)
    window = [r for r in a["requests"] if r["phase"] == "window"]
    lead = [r for r in a["requests"] if r["phase"] == "lead_in"]
    assert len(window) == 150 and len(lead) == 20
    assert min(r["due_s"] for r in window) == 0.0
    assert max(r["due_s"] for r in window) < 30.0
    assert all(-4.0 <= r["due_s"] < 0 for r in lead)
    assert all(len(r["prompt"]) + r["max_new"] <= 1280 for r in window)
    assert all(1 <= t < 32768 for r in window for t in r["prompt"])


@pytest.mark.parametrize("rate", [1.5, 4.0])
def test_committed_mix_generates_at_a_cells_rate(rate):
    mix = manifest.load_traffic({"traffic": "chat-steady",
                                 "traffic_overrides": {"rate_rps": rate}})
    plan = requests.generate(mix, 3, 10, 1000, 1280)
    window = [r for r in plan["requests"] if r["phase"] == "window"]
    assert len(window) == round(rate * 10)
    body = json.loads(plan["requests"][0]["body"])
    assert body["stream"] is True
    assert body["max_new_tokens"] == plan["requests"][0]["max_new"]


def test_gamma_arrivals_and_shared_prefixes_are_data_only():
    mix = dict(CHAT, arrivals={"process": "gamma", "cv": 3.0},
               shared_prefix={"pool": 4, "share": 0.75,
                              "tokens": {"dist": "uniform", "min": 64,
                                         "max": 96}})
    plan = requests.generate(mix, 11, 60, 32768, 1280)
    window = [r for r in plan["requests"] if r["phase"] == "window"]
    heads = {}
    for r in window:
        heads.setdefault(tuple(r["prompt"][:64]), 0)
        heads[tuple(r["prompt"][:64])] += 1
    shared = sum(n for n in heads.values() if n > 1)
    assert 0.5 * len(window) < shared <= len(window)
    gaps = np.diff([r["due_s"] for r in window])
    assert gaps.std() / gaps.mean() > 1.5        # burstier than Poisson


def test_training_batches_are_seeded_and_rows_differ():
    mix = {"generator": "batches", "seq": 32, "rows": "uniform_ids"}
    a = batches.generate(mix, 2 ** 33 + 1, 4, 500)
    b = batches.generate(mix, 2 ** 33 + 1, 4, 500)
    first, second = next(a), next(a)
    assert (first == next(b)).all() and first.shape == (4, 32)
    assert not (first == second).all()
    assert len({tuple(r) for r in first}) == 4
    assert first.min() >= 0 and first.max() < 500


# -- the load generator against a scripted server -------------------------

class _Scripted(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        n = body["max_new_tokens"]
        if body["tokens"][0] == 999:            # scripted refusal
            data = b'{"error": "nope"}'
            self.send_response(429)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(obj):
            data = json.dumps(obj).encode() + b"\n"
            self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))

        time.sleep(0.05)                        # "prefill"
        sent = 0
        while sent < n:                         # bursts of two
            k = min(2, n - sent)
            chunk({"tokens": list(range(sent, sent + k))})
            sent += k
            time.sleep(0.02)
        chunk({"done": True, "n_tokens": n})
        self.wfile.write(b"0\r\n\r\n")

    def log_message(self, *a):
        pass


@pytest.fixture()
def scripted_server():
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Scripted)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()
    t.join(timeout=5)


def _req(first_token, n):
    return {"prompt": [first_token, 2, 3], "max_new": n,
            "body": requests._body([first_token, 2, 3], n)}


def test_open_loop_times_from_the_due_time(scripted_server):
    t0 = time.monotonic() + 0.2
    plan = [(t0 + 0.1 * i, _req(1, 6)) for i in range(5)]
    recs = loadgen.run("127.0.0.1", scripted_server, plan)
    assert len(recs) == 5 and all(r.ok for r in recs)
    for r in recs:
        assert r.tokens == list(range(6)) and len(r.stamps) == 6
        assert 0 <= r.sent - r.due < 0.05           # lateness is recorded
        assert 0.04 < r.first - r.due < 0.3         # >= the scripted prefill
        # bursts of two: stamps come in pairs, the mean gap spans them
        assert r.stamps[0] == r.stamps[1] and r.stamps[2] > r.stamps[1]
        assert r.done is not None and r.end >= r.done


def test_a_refused_request_counts_as_failed(scripted_server):
    t0 = time.monotonic() + 0.1
    recs = loadgen.run("127.0.0.1", scripted_server,
                       [(t0, _req(999, 4)), (t0, _req(1, 4))])
    bad = [r for r in recs if not r.ok]
    assert len(bad) == 1 and bad[0].status == 429 and bad[0].error


def test_timers_run_from_the_loop_at_their_time(scripted_server):
    """The traced stretch is switched on and off by timers of the one
    loop: each runs once, at its time, between the sends."""
    t0 = time.monotonic() + 0.1
    fired = []
    recs = loadgen.run(
        "127.0.0.1", scripted_server,
        [(t0, _req(1, 2)), (t0 + 0.3, _req(1, 2))],
        timers=[(t0 + 0.15, lambda: fired.append(time.monotonic()))])
    assert len(fired) == 1 and 0.15 <= fired[0] - t0 < 0.3
    assert all(r.ok for r in recs) and recs[1].sent > fired[0]


def test_the_collector_is_off_inside_the_block_and_back_after(
        scripted_server):
    """A measuring caller enters ``collector_off`` before it fixes the
    due times (a collection takes its time in a large process); ``run``
    itself leaves the collector alone."""
    import gc
    assert gc.isenabled()
    seen = []
    with loadgen.collector_off():
        t0 = time.monotonic() + 0.1
        loadgen.run("127.0.0.1", scripted_server, [(t0, _req(1, 2))],
                    timers=[(t0, lambda: seen.append(gc.isenabled()))])
        assert not gc.isenabled() and gc.get_freeze_count() > 0
    assert gc.isenabled() and gc.get_freeze_count() == 0
    t0 = time.monotonic() + 0.1
    loadgen.run("127.0.0.1", scripted_server, [(t0, _req(1, 2))],
                timers=[(t0, lambda: seen.append(gc.isenabled()))])
    assert seen == [False, True] and gc.isenabled()


# -- the knee sweep's rule on made-up records ------------------------------

def _records(rate, step_s, lifetime, out_tokens, slots=None):
    """Requests due at ``rate`` a second for ``step_s`` seconds; each
    takes ``lifetime`` seconds once it has one of ``slots`` slots."""
    recs, free_at = [], [0.0] * (slots or 10 ** 6)
    for i in range(int(rate * step_s)):
        due = i / rate
        k = min(range(len(free_at)), key=free_at.__getitem__) \
            if slots else i
        start = max(due, free_at[k])
        free_at[k] = start + lifetime
        r = loadgen.Record({"max_new": out_tokens}, due)
        r.sent, r.status = due, 200
        r.stamps = [start + lifetime * (j + 1) / out_tokens
                    for j in range(out_tokens)]
        r.tokens = list(range(out_tokens))
        r.first, r.done = r.stamps[0], r.stamps[-1]
        r.end = r.done
        recs.append(r)
    return recs


@pytest.mark.parametrize("rate,slots,sustained", [
    (2.0, None, True),       # 20 in flight, nothing waits
    (2.0, 32, True),         # 32 slots hold 20
    (4.0, 32, False),        # 40 wanted of 32: the backlog grows
])
def test_knee_rule_tells_a_sustained_rate_from_a_growing_backlog(
        rate, slots, sustained):
    from benchmarks.tools import knee_sweep
    row = knee_sweep.judge(_records(rate, 60.0, 10.0, 100, slots), 0.0,
                           60.0)
    assert row["sustained"] is sustained
    assert row["failed"] == 0 and row["sent"] == int(rate * 60)
    if sustained:
        assert row["received_tokens"] >= 0.95 * row["asked_tokens"]
        assert abs(row["in_flight_last"] - rate * 10.0) < 2
    else:
        assert row["ttft_p50_ms_by_third"][2] > \
            row["ttft_p50_ms_by_third"][1] > 1000


def test_prometheus_text_sums_by_name():
    text = ('# HELP x\nskytpu_ttft_seconds_sum 1.5\n'
            'skytpu_ttft_seconds_count 3\n'
            'skytpu_programs_compiled_total{kind="a"} 2\n'
            'skytpu_programs_compiled_total{kind="b"} 5\n')
    got = loadgen.parse_prometheus(text)
    assert got["skytpu_programs_compiled_total"] == 7
    assert got["skytpu_ttft_seconds_sum"] == 1.5


# -- a listener that accepts late -------------------------------------------

class _LateListener:
    """A server whose accept queue holds ONE connection and which starts
    accepting ``late_s`` after it was made: the second connection's SYN
    is dropped and retried by the kernel a second later. Each accepted
    request gets ``n`` tokens, one every ``gap_s``."""

    def __init__(self, late_s, n=4, gap_s=0.05):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(0)
        self.port = self.sock.getsockname()[1]
        self.late_s, self.n, self.gap_s = late_s, n, gap_s
        self.accepted = []
        self.closing = False
        self.threads = [threading.Thread(target=self._accept, daemon=True)]
        self.threads[0].start()

    def _accept(self):
        time.sleep(self.late_s)
        self.sock.settimeout(0.05)
        while not self.closing:
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.settimeout(None)
            self.accepted.append(time.monotonic())
            t = threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True)
            self.threads.append(t)
            t.start()

    def _serve(self, conn):
        with conn:
            data = b""
            while b"\r\n\r\n" not in data:
                data += conn.recv(65536)
            conn.sendall(b"HTTP/1.1 200 OK\r\n"
                         b"Transfer-Encoding: chunked\r\n\r\n")
            for i in range(self.n):
                line = json.dumps({"tokens": [i]}).encode() + b"\n"
                conn.sendall(b"%x\r\n%s\r\n" % (len(line), line))
                time.sleep(self.gap_s)
            line = b'{"done": true}\n'
            conn.sendall(b"%x\r\n%s\r\n0\r\n\r\n" % (len(line), line))

    def close(self):
        self.closing = True
        for t in self.threads:
            t.join(timeout=5)
        self.sock.close()


@pytest.mark.parametrize("poll", [False, True])
def test_a_late_accept_delays_one_request_and_no_stamp(poll):
    """Four requests a twentieth of a second apart at a listener that
    starts accepting 0.4 s late and queues one connection: the kernel
    retries the others' connects a second later. The loop must not wait
    in any of them — the first request's tokens, streamed from 0.4 s on,
    are stamped as they come, and every request is taken up at its due
    time. (A ``send`` that connects and writes in the loop sits in the
    second request's connect until ~1 s and stamps the first one's four
    tokens together.) The same whether the loop waits between events
    or polls."""
    srv = _LateListener(late_s=0.4)
    try:
        t0 = time.monotonic() + 0.1
        info = {}
        recs = loadgen.run("127.0.0.1", srv.port,
                           [(t0 + 0.05 * i, _req(1, 4)) for i in range(4)],
                           loop_info=info, poll=poll)
    finally:
        srv.close()
    assert all(r.ok for r in recs), [r.error for r in recs]
    first = min(recs, key=lambda r: r.first)
    # read as they came: three gaps of 50 ms, not one late read
    assert first.stamps[-1] - first.stamps[0] > 0.1
    assert first.first - srv.accepted[0] < 0.8
    # every request was taken up when it was due (the kernel's retry is a
    # second; the margins are for a loaded test machine); what waited
    # for the listener is its own hand-over, and is recorded as that
    assert all(r.started - r.due < 0.8 for r in recs)
    assert info["taken_up_late_max"] < 800 and info["turn_max"] < 800
    assert info["send_call_max"] < 800
    late = [r for r in recs if r.sent - r.started > 0.3]
    assert late and info["handover_max"] > 300


def test_a_connect_that_never_completes_fails_that_request(monkeypatch):
    """The listener's queue is full for longer than the connect limit:
    the requests behind the first fail as refused ones do (attempted,
    failed, no latency), and the loop goes on to finish the first."""
    monkeypatch.setattr(loadgen, "CONNECT_TIMEOUT_S", 0.3)
    srv = _LateListener(late_s=1.0)
    try:
        t0 = time.monotonic() + 0.1
        recs = loadgen.run("127.0.0.1", srv.port,
                           [(t0 + 0.02 * i, _req(1, 4)) for i in range(3)])
    finally:
        srv.close()
    assert [r.ok for r in recs] == [True, False, False]
    for r in recs[1:]:
        assert "timed out" in r.error and r.status == 0 and r.sent is None
        assert 0.3 <= r.end - r.started < 0.9
