"""Serving fault tolerance (docs/robustness.md §Replica loss & rolling
update): engine crash recovery, graceful drain, and mid-stream LB
failover, chaos-verified.

The headline guarantees:
* an unrecoverable device error at ANY dispatch seam (admit wave,
  prefill chunk, decode burst, spec verify, KV block alloc) resets the
  engine and re-admits every in-flight request through the preemption
  resume path — greedy output BIT-IDENTICAL to a fault-free run,
  across {fp32, int8 KV} x {spec on/off} x {adapters on/off};
* a crash leaks nothing: KV blocks return to the pool, adapter pins
  release, drafter slots free;
* ``POST /drain`` stops admissions (typed 503 + Retry-After, body
  consumed on keep-alive), finishes in-flight work, and flips
  ``/healthz`` to draining (degraded past the deadline) so the LB and
  controller stop routing BEFORE the kill;
* the LB resumes a died-mid-stream generation on a surviving replica
  by replaying prompt + committed tokens with a reduced budget — the
  client sees ONE gapless, duplicate-free token sequence;
* the serve tier drains a replica before terminating it, and the CLI
  reads a planned drain as exit 0, a stuck one as exit 2.
"""

import http.client
import http.server
import json
import socket
import threading
import time
import urllib.error
import urllib.request
from urllib.parse import urlsplit

import jax
import numpy as np
import pytest

from skypilot_tpu import chaos
from skypilot_tpu.infer import adapters as ad
from skypilot_tpu.infer import draft as draft_lib
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import server as srv
from skypilot_tpu.models import llama
from skypilot_tpu.observability import flight as fl
from skypilot_tpu.observability import forensics
from skypilot_tpu.observability import health as health_lib
from skypilot_tpu.serve import load_balancer, serve_state

CFG = llama.CONFIGS["llama3-tiny"]
PROMPT_LEN = 12   # > prefill_chunk=8: chunk-admitted, resume-covered
NEW_TOKENS = 8


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos._reset_for_tests()
    yield
    chaos._reset_for_tests()


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def distilled(params):
    """(target, draft_params, draft_cfg) at the self-distillation
    endpoint — high acceptance without a training run."""
    return draft_lib.self_distilled_pair(params, CFG, 1)


def _prompts(n=3, length=PROMPT_LEN, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, length).tolist()
            for _ in range(n)]


def _mk_adapter_params(seed, rank=4, scale=0.05):
    r = np.random.default_rng(seed)
    L = CFG.n_layers
    out = {}
    for t, (sa, sb) in ad.target_shapes(CFG, rank).items():
        sa = sa[:-1] + (rank,)
        sb = (rank,) + sb[1:]
        out[t] = {"a": r.normal(size=(L,) + sa).astype(np.float32)
                  * scale,
                  "b": r.normal(size=(L,) + sb).astype(np.float32)
                  * scale}
    return out


def _catalog(register=2):
    cat = ad.AdapterCatalog(CFG, n_adapters=4, rank=4)
    for i in range(register):
        cat.register(f"ft-{i}", params=_mk_adapter_params(100 + i))
    return cat


def _drive(e, max_burst=4, max_steps=500):
    """Run the engine dry, recovering through every typed dispatch
    crash (a crash is an involuntary preemption). Returns the number
    of recoveries taken."""
    recovered = 0
    for _ in range(max_steps):
        if not (e.waiting or e.chunking or e.slot_req):
            return recovered
        try:
            e.step_burst(max_burst=max_burst)
        except eng.EngineDispatchError as ex:
            e.recover(ex)
            recovered += 1
    raise AssertionError("engine failed to drain")


def _run_batch(e, prompts, adapter=None):
    ids = [e.add_request(list(p), max_new_tokens=NEW_TOKENS,
                         adapter=adapter)
           for p in prompts]
    recovered = _drive(e)
    by_rid = {r.rid: r for r in e.finished}
    assert all(i in by_rid for i in ids)
    return [list(by_rid[i].tokens) for i in ids], recovered


def _recoveries_total():
    return sum(c.value for _, c in eng.ENGINE_RECOVERIES.children())


# ---------------------------------------------------------------------------
# Engine crash recovery: bit-identical resume across the full matrix.


@pytest.mark.parametrize("kv_int8,spec,adapters", [
    (False, False, False), (False, False, True),
    (False, True, False), (False, True, True),
    (True, False, False), (True, False, True),
    (True, True, False), (True, True, True),
])
def test_crash_resume_parity_matrix(params, distilled, kv_int8, spec,
                                    adapters):
    """A seeded chaos fault at the decode (spec: verify) seam mid-run
    resets and resumes every in-flight request with BIT-IDENTICAL
    greedy output, leaking neither KV blocks nor adapter pins —
    across {fp32, int8 KV} x {spec on/off} x {adapters on/off}."""
    kw = dict(n_slots=2, max_len=48, prompt_buckets=(16,),
              prefill_chunk=8, prefix_pool=4, kv_block=16,
              max_wave=2, pad_waves=True, kv_int8=kv_int8)
    eng_params = params
    if spec:
        target, dparams, dcfg = distilled
        eng_params = target
        kw.update(spec_k=4,
                  draft_engine=draft_lib.DraftEngine(
                      dparams, dcfg, n_slots=2, max_len=48,
                      kv_int8=kv_int8))
    cat = _catalog() if adapters else None
    e = eng.InferenceEngine(eng_params, CFG, adapters=cat, **kw)
    prompts = _prompts()
    adapter = "ft-0" if adapters else None

    want, _ = _run_batch(e, prompts, adapter=adapter)
    assert all(len(t) == NEW_TOKENS for t in want)
    e.reset()
    e.clear_prefix_cache()

    seam = "verify" if spec else "decode"
    chaos.configure({"seed": 7, "faults": [
        {"point": "engine.dispatch", "match": {"seam": seam},
         "times": 1}]})
    before = _recoveries_total()
    got, recovered = _run_batch(e, prompts, adapter=adapter)
    inj = chaos.injector()
    chaos.deactivate()

    assert len(inj.fired) == 1
    assert recovered == 1
    assert _recoveries_total() == before + 1
    assert got == want
    # Nothing leaked across the reset: blocks back in the pool once
    # the prefix cache lets go, adapter pins released.
    e.clear_prefix_cache()
    assert e.blocks_used == 0
    assert all(not r.adapter_pinned for r in e.finished)
    if cat is not None:
        assert all(cat.pins(s) == 0 for s in range(cat.n_adapters))


def test_crash_at_admit_seam_recovers(params):
    """A device error during the admission wave (short prompts, no
    chunked prefill) is the same recoverable crash: the victims had
    committed nothing, re-admit from scratch, parity exact."""
    def mk():
        return eng.InferenceEngine(params, CFG, n_slots=2, max_len=32,
                                   prompt_buckets=(8,), kv_block=16)
    prompts = _prompts(length=4, seed=3)
    want, _ = _run_batch(mk(), prompts)

    chaos.configure({"seed": 5, "faults": [
        {"point": "engine.dispatch", "match": {"seam": "admit"},
         "times": 1}]})
    e = mk()
    got, recovered = _run_batch(e, prompts)
    fired = chaos.injector().fired
    chaos.deactivate()
    assert len(fired) == 1 and fired[0]["ctx"]["seam"] == "admit"
    assert recovered == 1 and got == want
    assert e.blocks_used == 0


def test_kv_alloc_fault_recovers_typed(params):
    """A fault at the KV block-allocation point surfaces as a typed
    recoverable EngineDispatchError (the alloc runs inside the
    admit/chunk boundary), never a raw ChaosError, and the run still
    finishes bit-identical."""
    def mk():
        return eng.InferenceEngine(params, CFG, n_slots=2, max_len=48,
                                   prompt_buckets=(16,),
                                   prefill_chunk=8, kv_block=16)
    prompts = _prompts(seed=11)
    want, _ = _run_batch(mk(), prompts)

    chaos.configure({"seed": 2, "faults": [
        {"point": "kv.alloc", "times": 1}]})
    e = mk()
    got, recovered = _run_batch(e, prompts)
    fired = chaos.injector().fired
    chaos.deactivate()
    assert len(fired) == 1
    assert recovered >= 1 and got == want
    assert e.blocks_used == 0


def test_crash_mid_chunk_releases_blocks_and_adapter_pins(params):
    """Leak audit, crash mid prefill-chunk on an adapter engine: after
    recovery and completion the block pool returns to empty and no
    adapter pool slot stays pinned."""
    cat = _catalog()
    e = eng.InferenceEngine(params, CFG, adapters=cat, n_slots=2,
                            max_len=48, prompt_buckets=(16,),
                            prefill_chunk=8, prefix_pool=4,
                            kv_block=16)
    chaos.configure({"seed": 9, "faults": [
        {"point": "engine.dispatch", "match": {"seam": "chunk"},
         "times": 1}]})
    out, recovered = _run_batch(e, _prompts(seed=4), adapter="ft-1")
    chaos.deactivate()
    assert recovered == 1
    assert all(len(t) == NEW_TOKENS for t in out)
    assert all(not r.adapter_pinned for r in e.finished)
    assert all(cat.pins(s) == 0 for s in range(cat.n_adapters))
    e.clear_prefix_cache()
    assert e.blocks_used == 0


class _DeadToken:
    """Stands in for the output of a chunk program the device failed."""

    def block_until_ready(self):
        raise RuntimeError("device halted in a queued chunk")


@pytest.mark.parametrize("where", ["next_chunk", "burst", "reset"])
def test_a_failed_queued_chunk_surfaces_at_its_landing(params, where):
    """A non-final chunk is dispatched and not awaited (PR 47), so a
    device failure in it shows when it is landed: before the next chunk
    but one, or by the burst dispatched behind it — the same typed,
    recoverable ``EngineDispatchError`` of the ``chunk`` seam — and
    ``recover()`` / ``reset()`` leave no handle behind; the recovered
    run's tokens are the uncrashed run's."""
    def mk():
        return eng.InferenceEngine(params, CFG, n_slots=2, max_len=64,
                                   prompt_buckets=(16, 32),
                                   prefill_chunk=8, kv_block=16)
    long_prompt = _prompts(n=1, length=30, seed=8)[0]     # 4 chunks
    want = mk().generate([[7, 8, 9], long_prompt],
                         max_new_tokens=NEW_TOKENS)
    e = mk()
    short = e.add_request([7, 8, 9], max_new_tokens=NEW_TOKENS)
    e.admit()
    long = e.add_request(long_prompt, max_new_tokens=NEW_TOKENS)
    e.admit()
    assert e.slot_req and e.chunking
    assert e.prefill_chunk_step()
    assert len(e._queued_chunks) == 1
    e._queued_chunks[0].tok = _DeadToken()
    if where == "reset":
        e.reset()
        assert not e._queued_chunks and not e.chunking
        return
    with pytest.raises(eng.EngineDispatchError) as err:
        if where == "burst":
            e.complete_decode_burst(e.dispatch_decode_burst(max_burst=2))
        else:
            e.prefill_chunk_step()     # one running, one queued: fine
            e.prefill_chunk_step()     # lands the dead one first
    assert err.value.seam == "chunk" and err.value.recoverable
    assert e.recover(err.value) == 2
    assert not e._queued_chunks and not e.chunking
    assert _drive(e) == 0
    by_rid = {r.rid: list(r.tokens) for r in e.finished}
    assert [by_rid[short], by_rid[long]] == want
    assert e.blocks_used == 0


def test_crash_mid_verify_releases_drafter_slots(params, distilled):
    """Leak audit, crash mid spec-verify: every drafter slot is free
    after the recovered run — the draft engine's claims died with the
    reset instead of wedging future admissions."""
    target, dparams, dcfg = distilled
    de = draft_lib.DraftEngine(dparams, dcfg, n_slots=2, max_len=48)
    e = eng.InferenceEngine(target, CFG, n_slots=2, max_len=48,
                            prompt_buckets=(16,), prefill_chunk=8,
                            kv_block=16, spec_k=4, draft_engine=de)
    chaos.configure({"seed": 13, "faults": [
        {"point": "engine.dispatch", "match": {"seam": "verify"},
         "times": 1}]})
    out, recovered = _run_batch(e, _prompts(seed=5))
    chaos.deactivate()
    assert recovered == 1
    assert all(len(t) == NEW_TOKENS for t in out)
    assert all(not de.claimed(s) for s in range(de.n_slots))


def test_recover_ledger_names_stall_recover(params):
    """Forensics: a crash victim's critical-path ledger carries the
    requeued outage as a NAMED stall_recover phase, and the phases
    still sum to the wall — the recovery window is attributed, not
    smeared into host_other."""
    e = eng.InferenceEngine(params, CFG, n_slots=2, max_len=48,
                            prompt_buckets=(16,), prefill_chunk=8,
                            kv_block=16,
                            flight_recorder=fl.FlightRecorder())
    chaos.configure({"seed": 21, "faults": [
        {"point": "engine.dispatch", "match": {"seam": "decode"},
         "times": 1}]})
    _run_batch(e, _prompts(seed=6))
    chaos.deactivate()
    victims = [r for r in e.finished if r.recoveries >= 1]
    assert victims
    ledger = forensics.ledger_from_records(victims[0].rid,
                                           e.flight.tail())
    assert ledger is not None
    names = {p["phase"] for p in ledger["phases"]}
    assert "stall_recover" in names
    total = sum(p["ms"] for p in ledger["phases"])
    assert total == pytest.approx(ledger["wall_ms"], abs=0.05)


# ---------------------------------------------------------------------------
# Model server: graceful drain lifecycle + crash-recovery storm guard.


class _SlowEngine:
    """Engine double: one token per slot per decode burst, with a
    per-burst delay so requests stay in flight while the test walks
    the drain lifecycle around them."""

    def __init__(self, n_slots=2, delay_s=0.0):
        self.n_slots = n_slots
        self.delay_s = delay_s
        self.waiting = []
        self.slot_req = {}
        self.finished = []
        self.free_slots = list(range(n_slots))
        self.buckets = (16,)
        self._rid = 0
        self.reset_calls = 0

    def add_request(self, tokens, max_new):
        r = eng.Request(rid=self._rid, prompt=list(tokens),
                        max_new_tokens=max_new)
        self._rid += 1
        self.waiting.append(r)
        return r.rid

    def admit(self, on_wave=None):
        while self.waiting and self.free_slots:
            r = self.waiting.pop(0)
            r.slot = self.free_slots.pop(0)
            r.tokens.append(7)
            r.first_token_s = time.time()
            self.slot_req[r.slot] = r
            if on_wave:
                on_wave()

    def decode_burst(self, max_burst=8):
        if self.delay_s:
            time.sleep(self.delay_s)
        for slot, r in list(self.slot_req.items()):
            r.tokens.append(8)
            if len(r.tokens) >= r.max_new_tokens:
                self.slot_req.pop(slot)
                self.free_slots.append(slot)
                self.finished.append(r)
        return {}

    def generate(self, prompts, max_new_tokens=2):
        return [[1] * max_new_tokens for _ in prompts]

    def reset(self):
        self.reset_calls += 1
        self.waiting.clear()
        self.slot_req.clear()
        self.finished.clear()
        self.free_slots = list(range(self.n_slots))


def _spawn_model_server(engine, **kw):
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    model, httpd = srv.serve(engine, host="127.0.0.1", port=port, **kw)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert model._ready.wait(timeout=60)
    return model, httpd, f"http://127.0.0.1:{port}"


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_drain_lifecycle():
    """The full rolling-update dance on one replica: healthy -> drain
    requested mid-flight -> admissions 503 typed (body consumed on a
    keep-alive socket) -> /health 503 + /healthz draining -> in-flight
    request still completes -> /drain polls to drained, deadline
    stable across idempotent repeats."""
    fake = _SlowEngine(n_slots=2, delay_s=0.02)
    model, httpd, url = _spawn_model_server(fake, max_burst=1)
    try:
        with urllib.request.urlopen(f"{url}/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "healthy"
        # Malformed drain body: typed 400, state untouched.
        code, out = _post(f"{url}/drain", [1, 2])
        assert code == 400 and not model.draining()

        result = {}

        def client():
            result["resp"] = _post(f"{url}/generate",
                                   {"tokens": [1, 2],
                                    "max_new_tokens": 40})

        t = threading.Thread(target=client)
        t.start()
        deadline = time.time() + 30
        while model.queue_depth() == 0 and time.time() < deadline:
            time.sleep(0.005)
        assert model.queue_depth() > 0

        code, st = _post(f"{url}/drain", {"grace_s": 20})
        assert code == 200
        assert st["draining"] and not st["drained"]
        assert st["in_flight"] >= 1
        deadline_s = st["deadline_s"]

        # New admissions shed typed on a KEEP-ALIVE connection — and
        # the connection stays parseable afterwards (the body was
        # consumed, not left to corrupt the next request).
        parts = urlsplit(url)
        conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                          timeout=30)
        body = json.dumps({"tokens": [3], "max_new_tokens": 4}).encode()
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 503
        assert r.getheader("Retry-After") == "1"
        shed = json.loads(r.read())
        assert shed["error"]["type"] == "draining"
        conn.request("GET", "/healthz")
        r2 = conn.getresponse()
        hz = json.loads(r2.read())
        assert hz["status"] == "draining"
        assert "in flight" in hz["reason"]
        conn.close()

        # /health flips 503 so the LB/controller stop routing here.
        try:
            urllib.request.urlopen(f"{url}/health", timeout=30)
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers.get("Retry-After") == "1"
            assert json.loads(e.read())["status"] == "draining"

        # The in-flight request FINISHES — drain sheds admissions,
        # never work already accepted.
        t.join(timeout=60)
        code, out = result["resp"]
        assert code == 200 and len(out["tokens"]) == 40

        deadline = time.time() + 30
        st = model.drain_status()
        while not st["drained"] and time.time() < deadline:
            time.sleep(0.02)
            code, st = _post(f"{url}/drain", {"grace_s": 20})
        assert st["drained"] and st["in_flight"] == 0
        # Idempotent: the repeat polls kept the FIRST deadline.
        assert st["deadline_s"] == deadline_s
    finally:
        model.shutdown()
        httpd.shutdown()


def test_drain_past_deadline_degrades_healthz():
    """A drain that cannot finish inside its grace window self-reports
    degraded on /healthz — which rolls up to `skytpu status --health`
    exit 2 (a stuck rolling update is an incident, a progressing one
    is not)."""
    fake = _SlowEngine(n_slots=1, delay_s=0.02)
    model, httpd, url = _spawn_model_server(fake, max_burst=1)
    try:
        p = model._add([1], 10 ** 6)        # never finishes
        deadline = time.time() + 30
        while model.queue_depth() == 0 and time.time() < deadline:
            time.sleep(0.005)
        code, st = _post(f"{url}/drain", {"grace_s": 0})
        assert code == 200 and st["draining"]
        time.sleep(0.05)
        with urllib.request.urlopen(f"{url}/healthz", timeout=30) as r:
            hz = json.loads(r.read())
        assert hz["status"] == "degraded"
        assert "past deadline" in hz["reason"]
        del p
    finally:
        model.shutdown()
        httpd.shutdown()


class _DeviceGone(RuntimeError):
    recoverable = True
    seam = "decode"


class _CrashLoopEngine(_SlowEngine):
    """Raises a recoverable device error on every decode burst while
    work is in flight; recover() requeues the victims — the crash
    repeats until the server's storm guard gives up."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.recover_calls = 0

    def decode_burst(self, max_burst=8):
        if self.slot_req:
            raise _DeviceGone("HBM parity storm")
        return {}

    def recover(self, exc=None):
        self.recover_calls += 1
        victims = list(self.slot_req.values())
        self.slot_req.clear()
        self.free_slots = list(range(self.n_slots))
        for r in victims:
            r.slot = None
            self.waiting.append(r)
        return len(victims)


def test_recovery_storm_guard_fails_over_to_reset(monkeypatch):
    """A crash LOOP must not recover forever: past the rolling-window
    storm limit the server stops resetting-and-requeuing, fails the
    in-flight requests typed, and does a plain reset — bounded victim
    retries instead of an invisible livelock."""
    monkeypatch.setenv("SKYTPU_RECOVERY_STORM_LIMIT", "2")
    fake = _CrashLoopEngine(n_slots=1)
    model = srv.ModelServer(fake, max_burst=4)
    try:
        p = model._add([1], 8)
        assert p.event.wait(timeout=30)
        assert "error" in (p.result or {})
        # Exactly limit recoveries were attempted, then the guard
        # routed to the fail-all path (which resets the engine).
        assert fake.recover_calls == 2
        assert fake.reset_calls >= 1
        assert model._ready.is_set()
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# Load balancer: mid-stream failover onto a surviving replica.


def _tok(pos):
    """The scripted replicas' shared greedy function: the token at
    CONTEXT POSITION pos. Replaying prompt+committed on any replica
    continues the same sequence — the determinism mid-stream failover
    leans on."""
    return (pos * 37 + 11) % 997


class _Scripted(http.server.BaseHTTPRequestHandler):
    """A scripted streaming replica. Fault switches are CLASS state
    shared by every replica in the service, so 'the first replica the
    policy picks dies once' is deterministic regardless of selection
    order."""

    protocol_version = "HTTP/1.1"
    bodies = []
    die_after = None       # emit N token lines, then cut the socket
    die_drop_done = False  # emit ALL tokens, then die before done
    boom_first = False     # 500 the first request (connect phase)
    died = 0

    @classmethod
    def reset(cls):
        cls.bodies = []
        cls.die_after = None
        cls.die_drop_done = False
        cls.boom_first = False
        cls.died = 0

    def _chunk(self, obj):
        data = json.dumps(obj).encode() + b"\n"
        self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
        self.wfile.flush()

    def _die(self):
        # close() alone won't send FIN while rfile/wfile still hold
        # makefile refs on the socket — shutdown() makes the death
        # visible to the LB immediately.
        try:
            self.connection.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.connection.close()

    def do_POST(self):
        cls = type(self)
        n = int(self.headers.get("Content-Length") or 0)
        fields = json.loads(self.rfile.read(n) or b"{}")
        cls.bodies.append(fields)
        if cls.boom_first:
            cls.boom_first = False
            out = b"exploded"
            self.send_response(500)
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)
            return
        start = len(fields["tokens"])
        budget = int(fields["max_new_tokens"])
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        for i in range(budget):
            if (cls.die_after is not None and cls.died == 0
                    and i >= cls.die_after):
                cls.died = 1
                self._die()   # abrupt: no terminal chunk
                return
            self._chunk({"tokens": [_tok(start + i)]})
        if cls.die_drop_done and cls.died == 0:
            cls.died = 1
            self._die()
            return
        self._chunk({"done": True, "n_tokens": budget})
        self.wfile.write(b"0\r\n\r\n")

    def finish(self):
        try:
            super().finish()
        except Exception:  # noqa: BLE001 — scripted abrupt close
            pass

    def log_message(self, *a):
        pass


class _QuietServer(http.server.ThreadingHTTPServer):
    daemon_threads = True

    def handle_error(self, request, client_address):
        pass


@pytest.fixture()
def lb2(tmp_path, monkeypatch):
    """An LB over TWO scripted replicas."""
    yield from _mk_lb(tmp_path, monkeypatch, n_replicas=2)


@pytest.fixture()
def lb1(tmp_path, monkeypatch):
    """An LB over ONE scripted replica (candidate exhaustion)."""
    yield from _mk_lb(tmp_path, monkeypatch, n_replicas=1)


def _mk_lb(tmp_path, monkeypatch, n_replicas):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path / "home"))
    _Scripted.reset()
    serve_state.add_service("rec", {}, {}, 0)
    replicas = []
    for i in range(n_replicas):
        httpd = _QuietServer(("127.0.0.1", 0), _Scripted)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        serve_state.upsert_replica(
            "rec", i + 1, f"r{i + 1}", serve_state.ReplicaStatus.READY,
            f"http://127.0.0.1:{httpd.server_address[1]}")
        replicas.append(httpd)
    lb_httpd = load_balancer._ThreadingServer(
        ("127.0.0.1", 0),
        load_balancer.make_handler("rec",
                                   load_balancer.LeastLoadPolicy()))
    threading.Thread(target=lb_httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{lb_httpd.server_address[1]}"
    lb_httpd.shutdown()
    for r in replicas:
        r.shutdown()


def _lb_stream(lb_url, payload, timeout=30):
    """POST a streaming generate through the LB; returns the parsed
    NDJSON objects. read() raises on a truncated chunked body, so a
    normal return PROVES the terminal chunk arrived."""
    parts = urlsplit(lb_url)
    conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                      timeout=timeout)
    conn.request("POST", "/generate",
                 body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200
    body = r.read()
    conn.close()
    return [json.loads(ln) for ln in body.split(b"\n") if ln.strip()]


def _fo(phase):
    return load_balancer.LB_FAILOVERS.labels(phase=phase).value


def test_lb_mid_stream_failover_gapless(lb2):
    """A replica dying mid-stream is invisible to the client: the LB
    replays prompt + committed tokens on the survivor with a reduced
    budget and the stitched stream is gapless and duplicate-free."""
    _Scripted.die_after = 4
    before = _fo("mid_stream")
    prompt = [5, 9, 2, 7, 1]
    objs = _lb_stream(lb2, {"tokens": prompt, "max_new_tokens": 12,
                            "stream": True})
    want = [_tok(len(prompt) + i) for i in range(12)]
    got = [t for o in objs for t in o.get("tokens", [])]
    assert got == want
    done = objs[-1]
    assert done["done"] and done["n_tokens"] == 12
    assert done["failovers"] == 1
    assert _fo("mid_stream") == before + 1
    # The survivor was handed EXACTLY prompt + committed, with the
    # budget reduced by what already streamed.
    replay = _Scripted.bodies[-1]
    assert replay["tokens"] == prompt + want[:4]
    assert replay["max_new_tokens"] == 8


def test_lb_connect_phase_failover(lb2):
    """A replica that 500s before any byte streams costs a connect-
    phase failover, not a client-visible error: the next candidate
    serves the whole generation."""
    _Scripted.boom_first = True
    before = _fo("connect")
    prompt = [4, 4, 4]
    objs = _lb_stream(lb2, {"tokens": prompt, "max_new_tokens": 6,
                            "stream": True})
    got = [t for o in objs for t in o.get("tokens", [])]
    assert got == [_tok(3 + i) for i in range(6)]
    assert objs[-1]["done"] and objs[-1]["failovers"] == 1
    assert _fo("connect") == before + 1


def test_lb_exhausted_candidates_typed_in_stream_error(lb1):
    """No survivor left: the stream ends with a typed in-stream
    upstream_lost error AND a clean terminal chunk — a parseable
    failure, never a truncation the client must infer from framing."""
    _Scripted.die_after = 2
    objs = _lb_stream(lb1, {"tokens": [1, 2], "max_new_tokens": 6,
                            "stream": True})
    got = [t for o in objs for t in o.get("tokens", [])]
    assert got == [_tok(2), _tok(3)]
    err = objs[-1]["error"]
    assert err["type"] == "upstream_lost"
    assert err["n_streamed"] == 2
    assert err["failovers"] == 1


def test_lb_full_budget_lost_done_line_minted(lb1):
    """The replica delivered the whole budget but died before its done
    line: the LB mints the trailer itself instead of replaying a
    zero-budget generation."""
    _Scripted.die_drop_done = True
    objs = _lb_stream(lb1, {"tokens": [6, 6], "max_new_tokens": 5,
                            "stream": True})
    got = [t for o in objs for t in o.get("tokens", [])]
    assert got == [_tok(2 + i) for i in range(5)]
    done = objs[-1]
    assert done["done"] and done["lb_minted"]
    assert done["n_tokens"] == 5 and done["failovers"] == 1


def test_lb_failover_disabled_env(tmp_path, monkeypatch):
    """SKYTPU_LB_FAILOVER=0 restores the raw-splice contract: a
    replica death mid-stream is a client-visible truncation and no
    failover is counted."""
    monkeypatch.setenv("SKYTPU_LB_FAILOVER", "0")
    gen = _mk_lb(tmp_path, monkeypatch, n_replicas=2)
    lb_url = next(gen)
    try:
        _Scripted.die_after = 2
        before = _fo("mid_stream") + _fo("connect")
        with pytest.raises((http.client.IncompleteRead,
                            http.client.HTTPException,
                            ConnectionError, OSError)):
            parts = urlsplit(lb_url)
            conn = http.client.HTTPConnection(parts.hostname,
                                              parts.port, timeout=30)
            conn.request(
                "POST", "/generate",
                body=json.dumps({"tokens": [1], "max_new_tokens": 6,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            r.read()
            raise ConnectionError("truncated body read as complete")
        assert _fo("mid_stream") + _fo("connect") == before
    finally:
        for _ in gen:
            pass


def test_lb_typed_503_carries_retry_after(tmp_path, monkeypatch):
    """Zero ready replicas: the streaming path sheds typed 503
    overloaded WITH Retry-After — a client can distinguish 'back off'
    from a replica 5xx without parsing prose."""
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path / "home"))
    serve_state.add_service("empty", {}, {}, 0)
    lb_httpd = load_balancer._ThreadingServer(
        ("127.0.0.1", 0),
        load_balancer.make_handler("empty",
                                   load_balancer.LeastLoadPolicy()))
    threading.Thread(target=lb_httpd.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{lb_httpd.server_address[1]}"
        code, out = _post(f"{url}/generate",
                          {"tokens": [1], "max_new_tokens": 4,
                           "stream": True})
        assert code == 503
        assert out["error"]["type"] == "overloaded"
        req = urllib.request.Request(
            f"{url}/generate",
            data=json.dumps({"tokens": [1], "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers.get("Retry-After") is not None
            e.read()
    finally:
        lb_httpd.shutdown()


def test_lb_chunked_request_411(lb2):
    """A chunked request body is a typed 411 + close: reading it is
    unimplemented, and NOT reading it would poison the keep-alive
    socket for the next request."""
    parts = urlsplit(lb2)
    with socket.create_connection((parts.hostname, parts.port),
                                  timeout=30) as s:
        s.sendall(b"POST /generate HTTP/1.1\r\n"
                  b"Host: lb\r\n"
                  b"Transfer-Encoding: chunked\r\n\r\n")
        data = b""
        while True:   # 411 closes the connection: read to EOF
            piece = s.recv(65536)
            if not piece:
                break
            data += piece
    assert b" 411 " in data.split(b"\r\n", 1)[0]
    assert b"length_required" in data


# ---------------------------------------------------------------------------
# Serve tier: the controller drains a replica BEFORE terminating it.


class _DrainEndpoint(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    calls = 0

    def do_POST(self):
        cls = type(self)
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        if self.path != "/drain":
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        cls.calls += 1
        body = json.dumps({
            "draining": True,
            "in_flight": 0 if cls.calls >= 2 else 1,
            "drained": cls.calls >= 2,
            "deadline_s": 0,
        }).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def _mk_manager(monkeypatch, tmp_path, service):
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path / "home"))
    monkeypatch.setenv("SKYTPU_SERVE_DRAIN_GRACE_S", "10")
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    serve_state.add_service(service, {}, {}, 0)
    return replica_managers.ReplicaManager(service, SkyServiceSpec(), {})


def test_terminate_replica_drains_before_kill(monkeypatch, tmp_path):
    """_terminate_replica flips the replica to DRAINING synchronously
    (instantly out of ready_urls: the LB stops routing BEFORE any
    kill), polls POST /drain until drained, and only then moves to
    SHUTTING_DOWN and removes it."""
    _DrainEndpoint.calls = 0
    httpd = _QuietServer(("127.0.0.1", 0), _DrainEndpoint)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        mgr = _mk_manager(monkeypatch, tmp_path, "drainsvc")
        serve_state.upsert_replica("drainsvc", 1, "c1",
                                   serve_state.ReplicaStatus.READY, url)
        assert serve_state.ready_urls("drainsvc") == [url]
        mgr._terminate_replica(1)
        # Synchronous part: DRAINING and unrouted immediately.
        (row,) = serve_state.list_replicas("drainsvc")
        assert row["status"] == serve_state.ReplicaStatus.DRAINING
        assert serve_state.ready_urls("drainsvc") == []
        deadline = time.time() + 30
        while (serve_state.list_replicas("drainsvc")
               and time.time() < deadline):
            time.sleep(0.02)
        assert serve_state.list_replicas("drainsvc") == []
        # Drained via polling: the first poll reported in-flight work,
        # so the manager waited for at least one more.
        assert _DrainEndpoint.calls >= 2
        mgr._pool.shutdown(wait=True)
    finally:
        httpd.shutdown()


def test_terminate_replica_immediate_kill_skips_drain(monkeypatch,
                                                      tmp_path):
    """drain=False (preemption, teardown): straight to SHUTTING_DOWN,
    zero /drain calls — the endpoint is already gone or going."""
    _DrainEndpoint.calls = 0
    mgr = _mk_manager(monkeypatch, tmp_path, "killsvc")
    serve_state.upsert_replica("killsvc", 1, "c1",
                               serve_state.ReplicaStatus.READY,
                               "http://127.0.0.1:1")
    mgr._terminate_replica(1, drain=False)
    (row,) = serve_state.list_replicas("killsvc") or [None]
    if row is not None:   # async removal may not have landed yet
        assert row["status"] == serve_state.ReplicaStatus.SHUTTING_DOWN
    deadline = time.time() + 30
    while (serve_state.list_replicas("killsvc")
           and time.time() < deadline):
        time.sleep(0.02)
    assert serve_state.list_replicas("killsvc") == []
    assert _DrainEndpoint.calls == 0
    mgr._pool.shutdown(wait=True)


def test_draining_excluded_from_capacity_and_probes(monkeypatch,
                                                    tmp_path):
    """A DRAINING replica is on its way out: it must not count toward
    scale capacity nor be probed (a probe failure would double-
    terminate it)."""
    mgr = _mk_manager(monkeypatch, tmp_path, "capsvc")
    serve_state.upsert_replica("capsvc", 1, "c1",
                               serve_state.ReplicaStatus.DRAINING,
                               "http://127.0.0.1:1")
    serve_state.upsert_replica("capsvc", 2, "c2",
                               serve_state.ReplicaStatus.READY,
                               "http://127.0.0.1:2")
    live = mgr._live_replicas()
    assert [r["replica_id"] for r in live] == [2]

    probed = []
    monkeypatch.setattr(mgr, "_cluster_gone", lambda name: False)
    monkeypatch.setattr(mgr, "_probe_one",
                        lambda r: probed.append(r["replica_id"]) or True)
    mgr.probe_all()
    assert probed == [2]
    mgr._pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# Fleet health + CLI: a planned drain is visible, not an incident.


def test_worst_ranks_draining_between_healthy_and_degraded():
    mk = health_lib.component
    comps = [mk("model-server", "s/1", health_lib.HEALTHY)]
    assert health_lib.worst(comps) == health_lib.HEALTHY
    comps.append(mk("model-server", "s/2", health_lib.DRAINING))
    assert health_lib.worst(comps) == health_lib.DRAINING
    comps.append(mk("model-server", "s/3", health_lib.DEGRADED))
    assert health_lib.worst(comps) == health_lib.DEGRADED
    comps.append(mk("model-server", "s/4", health_lib.DEAD))
    assert health_lib.worst(comps) == health_lib.DEAD


def test_probe_replica_draining_branch():
    """A DRAINING replica row probes the replica itself: within its
    deadline it self-reports draining; past it, degraded; no URL reads
    as draining without a probe."""
    class _Healthz(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        status = health_lib.DRAINING
        reason = "draining (2 in flight)"

        def do_GET(self):
            health_lib.write_healthz(self, type(self).status,
                                     reason=type(self).reason)

        def log_message(self, *a):
            pass

    httpd = _QuietServer(("127.0.0.1", 0), _Healthz)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        row = {"replica_id": 1,
               "status": serve_state.ReplicaStatus.DRAINING,
               "url": url}
        got = health_lib._probe_replica(row, "svc", timeout=5)
        assert got["status"] == health_lib.DRAINING
        assert "in flight" in got["reason"]

        _Healthz.status = health_lib.DEGRADED
        _Healthz.reason = "draining past deadline (2 in flight)"
        got = health_lib._probe_replica(row, "svc", timeout=5)
        assert got["status"] == health_lib.DEGRADED

        row["url"] = None
        got = health_lib._probe_replica(row, "svc", timeout=5)
        assert got["status"] == health_lib.DRAINING
    finally:
        httpd.shutdown()


def test_status_health_exit_codes(monkeypatch):
    """`skytpu status --health`: a fleet whose worst component is
    draining is a PLANNED rolling update (exit 0, '-' mark); degraded
    or dead is an incident (exit 2)."""
    from click.testing import CliRunner
    from skypilot_tpu.client import cli as cli_mod

    def payload(status):
        return {"status": status, "alerts": [], "components": [
            health_lib.component("model-server", "svc/1", status,
                                 reason="draining (1 in flight)")]}

    monkeypatch.setattr(cli_mod, "_fleet_fetch",
                        lambda need_metrics=True: (None,
                                                   payload("draining")))
    res = CliRunner().invoke(cli_mod.cli, ["status", "--health"])
    assert res.exit_code == 0
    assert "fleet: DRAINING" in res.output
    assert "-  model-server" in res.output

    monkeypatch.setattr(cli_mod, "_fleet_fetch",
                        lambda need_metrics=True: (None,
                                                   payload("degraded")))
    res = CliRunner().invoke(cli_mod.cli, ["status", "--health"])
    assert res.exit_code == 2


def test_top_serve_line_fault_tolerance_columns():
    """`skytpu top`: replicas mid-drain, the crash-recovery rate, and
    the LB failover rate show on the serve line while they happen —
    and ride the --json data dict under the same names."""
    from skypilot_tpu.client import cli as cli_mod

    def fams(req, rec, fo, drain):
        return {
            "skytpu_http_requests_total": {
                "type": "counter",
                "samples": [({"code": "200"}, float(req))]},
            "skytpu_server_draining": {
                "type": "gauge", "samples": [({}, float(drain))]},
            "skytpu_engine_recoveries_total": {
                "type": "counter",
                "samples": [({"seam": "decode"}, float(rec))]},
            "skytpu_lb_failovers_total": {
                "type": "counter",
                "samples": [({"phase": "mid_stream"}, float(fo))]},
        }

    payload = {"status": "draining", "components": [], "alerts": []}
    now = 1000.0
    rendered, data = cli_mod._top_frame(
        fams(0, 0, 0, 0), now - 10.0, fams(10, 5, 3, 2), now, payload)
    serve_line = next(ln for ln in rendered.splitlines()
                      if ln.startswith("serve"))
    assert "drain 2" in serve_line
    assert "recov 0.50/s" in serve_line
    assert "failover 0.30/s" in serve_line
    assert data["serve"]["replicas_draining"] == 2
    assert data["serve"]["recoveries_per_s"] == pytest.approx(0.5)
    assert data["serve"]["failovers_per_s"] == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# The end-to-end chaos gate (bench_serve --failover, CI sizing).


def test_bench_failover_smoke():
    """The chaos-verified e2e gate: a seeded engine.dispatch fault and
    a seeded replica.kill against a 2-replica LB deployment — crash
    recovery AND mid-stream failover both bit-identical, zero lost
    requests."""
    from skypilot_tpu.infer import bench_serve
    r = bench_serve.run_failover_smoke()
    assert r["gate_ok"]
    assert r["crash_parity_ok"] and r["kill_parity_ok"]
    assert r["recoveries"] >= 1 and r["trailer_recoveries"] >= 1
    assert r["failovers"] >= 1 and r["trailer_failovers"] >= 1
    assert r["lost_requests"] == 0
