"""HTTP model server: health, generate, concurrency, bad input."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import server as srv
from skypilot_tpu.models import llama


@pytest.fixture(scope="module")
def model_server():
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    engine = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                                 prompt_buckets=(16,))
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    model, httpd = srv.serve(engine, host="127.0.0.1", port=port)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    assert model._ready.wait(timeout=300)  # warmup compile done
    yield f"http://127.0.0.1:{port}", params, cfg
    model.shutdown()
    httpd.shutdown()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_health(model_server):
    url, _, _ = model_server
    with urllib.request.urlopen(f"{url}/health", timeout=30) as r:
        assert json.loads(r.read())["status"] == "ok"


def test_generate_greedy_matches_engine(model_server):
    url, params, cfg = model_server
    prompt = [3, 17, 42]
    solo = eng.InferenceEngine(params, cfg, n_slots=1, max_len=64,
                               prompt_buckets=(16,))
    want = solo.generate([prompt], max_new_tokens=5)[0]
    code, out = _post(f"{url}/generate",
                      {"tokens": prompt, "max_new_tokens": 5})
    assert code == 200
    assert out["tokens"] == want
    assert out["ttft_ms"] is not None and out["total_ms"] > 0


def test_concurrent_generates(model_server):
    url, _, _ = model_server
    results = {}

    def one(i):
        code, out = _post(f"{url}/generate",
                          {"tokens": [i + 1, i + 2], "max_new_tokens": 4})
        results[i] = (code, len(out.get("tokens", [])))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(results[i] == (200, 4) for i in range(4))


def test_bad_requests(model_server):
    url, _, _ = model_server
    code, out = _post(f"{url}/generate", {"max_new_tokens": 4})
    assert code == 400
    code, out = _post(f"{url}/generate",
                      {"tokens": list(range(99)), "max_new_tokens": 2})
    assert code == 400  # prompt exceeds the largest bucket


def test_prompt_too_long_typed_400(model_server):
    """A prompt past the largest bucket is a CLIENT error: HTTP 400
    with a typed error body (never a 500), on both the blocking and
    the streaming path."""
    url, _, _ = model_server
    for payload in ({"tokens": list(range(99)), "max_new_tokens": 2},
                    {"tokens": list(range(99)), "max_new_tokens": 2,
                     "stream": True}):
        code, out = _post(f"{url}/generate", payload)
        assert code == 400
        err = out["error"]
        assert err["type"] == "prompt_too_long"
        assert err["prompt_len"] == 99 and err["max_prompt_len"] == 16
        assert "message" in err


def test_response_carries_cache_stats(model_server):
    """The response trailer reports per-request prefix-cache stats
    (this server runs without a pool: miss, zero cached tokens)."""
    url, _, _ = model_server
    code, out = _post(f"{url}/generate",
                      {"tokens": [4, 8, 15], "max_new_tokens": 3})
    assert code == 200
    assert out["cache_hit"] is False
    assert out["cached_tokens"] == 0
    assert out["prefill_chunks"] == 0
    # Spec stats ride the same trailer (this engine runs spec-off:
    # both zero, but the fields are always present).
    assert out["spec_drafted"] == 0
    assert out["spec_accepted"] == 0


def test_spec_trailer_on_blocking_and_stream_paths():
    """A speculative engine's per-request drafted/accepted stats reach
    the response trailer on BOTH the blocking result and the stream
    ``done`` chunk, and the spec'd output matches a spec-off engine
    token-for-token through the serving loop."""
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    prompt = [7, 8, 9] * 4
    plain = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                                prompt_buckets=(16,))
    want = plain.generate([prompt], max_new_tokens=8)[0]

    class AlwaysDraft:
        """One fixed draft token per burst: spec_drafted is provably
        nonzero end to end without depending on the random model's
        n-gram structure (rejected drafts roll back; parity holds)."""

        def __init__(self, req):
            pass

        def catch_up(self, prompt, generated):
            pass

        def draft(self, k):
            return [0][:k]

    engine = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                                 prompt_buckets=(16,), spec_k=3,
                                 spec_drafter=AlwaysDraft)
    engine.spec_min_rate = 0.0
    model = srv.ModelServer(engine, max_burst=4, open_burst=2)
    try:
        assert model._ready.wait(timeout=300)
        out = model.submit(prompt, 8)
        assert "error" not in out
        assert out["tokens"] == want
        assert out["spec_drafted"] > 0
        assert 0 <= out["spec_accepted"] <= out["spec_drafted"]

        chunks = list(model.submit_stream(prompt, 8))
        done = chunks[-1]
        assert "done" in done
        streamed = [t for c in chunks for t in c.get("tokens", [])]
        assert streamed == want
        assert done["spec_drafted"] > 0
        assert 0 <= done["spec_accepted"] <= done["spec_drafted"]
    finally:
        model.shutdown()


def test_server_loop_drives_chunked_prefill():
    """End to end through the serving loop: a prompt longer than the
    chunk admits via the chunk queue (interleaved with decode), the
    trailer reports the hit on a repeat, and tokens are identical
    warm vs cold."""
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    engine = eng.InferenceEngine(params, cfg, n_slots=2, max_len=64,
                                 prompt_buckets=(32,),
                                 prefill_chunk=8, prefix_pool=2)
    model = srv.ModelServer(engine, max_burst=4, open_burst=2)
    try:
        assert model._ready.wait(timeout=300)
        prompt = list(range(1, 13))              # 12 tokens, 2 chunks
        cold = model.submit(prompt, 4)
        assert "error" not in cold
        assert cold["cache_hit"] is False
        assert cold["prefill_chunks"] == 2
        warm = model.submit(prompt, 4)
        assert warm["cache_hit"] is True
        assert warm["cached_tokens"] == 8        # chunk-aligned prefix
        assert warm["prefill_chunks"] == 1       # suffix only
        assert warm["tokens"] == cold["tokens"]
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# A prompt's non-final chunks are dispatched and not awaited (PR 47).

_CHUNK = 8


def _events_since(path, t0_us):
    """The saved timeline's events that began at or after ``t0_us``
    (the buffer is the process's: other tests' events stay out)."""
    with open(path) as f:
        return sorted((ev for ev in json.load(f)["traceEvents"]
                       if ev.get("ts", 0) >= t0_us),
                      key=lambda ev: ev["ts"])


def _chunk_lens(n_chunks):
    """(warm-up prompt or None, the judged prompt): a cold prompt of n
    chunks, or for ONE chunk the repeat of a two-chunk prompt, whose
    first chunk is a prefix hit and whose suffix is the final chunk."""
    if n_chunks == 1:
        prompt = list(range(3, 3 + _CHUNK + 4))
        return prompt, prompt
    return None, list(range(3, 3 + (n_chunks - 1) * _CHUNK + 5))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.CONFIGS["llama3-tiny"]
    return llama.init_params(jax.random.key(0), cfg), cfg


def serve_through_the_loop(engine, prompt, new_tokens, live, path,
                           warm=None):
    """Serve ``prompt`` through the SERVER loop of ``engine`` — beside
    a short request that decodes all the while if ``live`` — and return
    (result, the loop's events from the submit on, whether the rider
    was still decoding when the result came). ``path`` is where
    ``SKYTPU_TIMELINE_FILE_PATH`` points. Other families' serve tests
    use it too."""
    from skypilot_tpu.utils import timeline
    model = srv.ModelServer(engine, max_burst=4, open_burst=2)
    try:
        assert model._ready.wait(timeout=300)
        if warm is not None:
            assert "error" not in model.submit(warm, 2)
        rider = None
        if live:
            # Rows are live under every chunk of the long prompt.
            rider = model.submit_stream([5, 6, 7], 100)
            assert "tokens" in next(rider)
        t0 = time.time() * 1e6
        out = model.submit(prompt, new_tokens)
        assert "error" not in out
        rode = bool(engine.slot_req) if live else None
        if rider is not None:
            list(rider)
        timeline.save_now()
        return out, _events_since(path, t0), rode
    finally:
        model.shutdown()


def check_chunk_order(events, n_chunks, live):
    """The loop's own order of events, for ONE prompt of ``n_chunks``:
    only the final chunk is fetched, every other is dispatched
    ``queued`` and landed, never two of them unlanded when the next is
    dispatched, and exactly one chunk lies between two bursts
    dispatched while the prompt chunked. Returns how many bursts said
    ``why="chunking"``."""
    def named(name):
        return [ev for ev in events if ev["name"] == name]

    dispatches = named("engine.chunk.dispatch")
    assert [d["args"]["final"] for d in dispatches] == \
        [0] * (n_chunks - 1) + [1]
    assert [d["args"]["queued"] for d in dispatches] == \
        [1] * (n_chunks - 1) + [0]
    fetches = named("engine.chunk.fetch")
    assert [f["args"]["final"] for f in fetches] == [1]
    assert fetches[0]["args"]["ttft_ms"] > 0
    assert len(named("engine.chunk.land")) == n_chunks - 1
    unlanded = since_burst = chunking_bursts = 0
    for ev in events:
        name, args = ev["name"], ev.get("args", {})
        if name == "engine.chunk.dispatch":
            assert unlanded <= 1, "two chunks unlanded at a dispatch"
            unlanded += 1
            since_burst += 1
        elif name in ("engine.chunk.land", "engine.chunk.fetch"):
            unlanded -= 1
        elif name == "engine.decode.dispatch" \
                and args.get("why") == "chunking":
            # One chunk since the burst before it: the alternation.
            assert since_burst == 1, (since_burst, chunking_bursts)
            since_burst = 0
            chunking_bursts += 1
        elif name == "engine.decode.dispatch" and since_burst:
            # The burst after the FINAL chunk: the prompt no longer
            # chunks, so it says "open"; it too follows one chunk where
            # rows were live, and all of them where no burst had
            # anything to decode before.
            assert since_burst == (1 if live else n_chunks)
            since_burst = 0
    assert unlanded == 0
    return chunking_bursts


def check_a_family_through_the_loop(make_engine, prompt, check_greedy,
                                    path):
    """A family that carries slot state from chunk to chunk (a
    recurrent state, a ring, conv tails), through the SERVER loop
    beside a request that decodes all the while: the four-chunk
    prompt's non-final chunks are dispatched and not awaited, the
    device runs them in dispatch order all the same — the tokens are
    the reference's (``check_greedy``) and the library loop's, one
    chunk lies between two bursts, only the final chunk is fetched."""
    out, events, rode = serve_through_the_loop(
        make_engine(), prompt, 8, True, path)
    assert rode, "the rider finished before the long prompt did"
    check_greedy(prompt, out["tokens"])
    assert out["tokens"] == make_engine().generate(
        [prompt], max_new_tokens=8)[0]
    assert out["prefill_chunks"] == 4
    assert check_chunk_order(events, 4, True) == 3
    assert [ev["args"]["carried"] for ev in events
            if ev["name"] == "engine.chunk.dispatch"] == [0, 1, 1, 1]


@pytest.fixture()
def timeline_path(tmp_path, monkeypatch):
    from skypilot_tpu.utils import timeline
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    return path


@pytest.mark.parametrize("live", [False, True], ids=["alone", "live_rows"])
@pytest.mark.parametrize("n_chunks", [1, 2, 3, 9])
def test_only_the_final_chunk_is_awaited(tiny_model, timeline_path,
                                         n_chunks, live):
    """The serve loop over a prompt of n chunks, with and without rows
    decoding beside it: the tokens are an unchunked engine's; the one
    ``engine.chunk.fetch`` is the final chunk's; every other chunk was
    dispatched ``queued`` and landed, never more than one of them
    unlanded when the next was dispatched; and between two bursts
    dispatched while the prompt chunked there is exactly one chunk."""
    params, cfg = tiny_model
    engine = eng.InferenceEngine(
        params, cfg, n_slots=3, max_len=128, prompt_buckets=(8, 128),
        prefill_chunk=_CHUNK, prefix_pool=2)
    warm, prompt = _chunk_lens(n_chunks)
    out, events, rode = serve_through_the_loop(
        engine, prompt, 6, live, timeline_path, warm=warm)
    plain = eng.InferenceEngine(params, cfg, n_slots=1, max_len=128,
                                prompt_buckets=(128,))
    assert out["tokens"] == plain.generate([prompt], max_new_tokens=6)[0]
    assert out["prefill_chunks"] == n_chunks
    assert not engine._queued_chunks
    chunking_bursts = check_chunk_order(events, n_chunks, live)
    if live:
        assert rode, "the rider finished before the long prompt did"
        assert chunking_bursts == n_chunks - 1
        # A queued chunk that ran beside live rows says so on its
        # flight record, which closes no earlier than its dispatch.
        chunks = [r for r in engine.flight.tail()
                  if r["burst"] == "chunk"][-n_chunks:]
        assert [r["queued"] for r in chunks] == \
            [1] * (n_chunks - 1) + [0]
        assert all(r.get("stall") for r in chunks)
        assert all(r["dispatch_wall_ms"] <= r["dur_s"] * 1e3 + 1e-6
                   for r in chunks)


def _post_stream(url, payload, timeout=300):
    """POST with stream:true; returns [(arrival_time, chunk_dict)]."""
    import time
    req = urllib.request.Request(
        url, data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    chunks = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200
        assert r.headers.get("Content-Type") == "application/x-ndjson"
        buf = b""
        while True:
            piece = r.read1(65536)
            if not piece:
                break
            buf += piece
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if line.strip():
                    chunks.append((time.time(), json.loads(line)))
    return chunks


def test_streaming_tokens_match_blocking(model_server):
    """Streamed chunks concatenate to exactly the blocking result, and
    the first token chunk lands BEFORE generation finishes (the whole
    point of streaming TTFT)."""
    url, _, _ = model_server
    prompt = [5, 9, 2]
    _, blocking = _post(f"{url}/generate",
                        {"tokens": prompt, "max_new_tokens": 24})
    chunks = _post_stream(f"{url}/generate",
                          {"tokens": prompt, "max_new_tokens": 24})
    assert "done" in chunks[-1][1]
    streamed = [t for _, c in chunks for t in c.get("tokens", [])]
    assert streamed == blocking["tokens"]
    assert chunks[-1][1]["ttft_ms"] is not None
    # Multiple emissions (burst=8 over 24 tokens -> >= 3 token chunks),
    # and the first arrives strictly before the done chunk.
    token_chunks = [c for _, c in chunks if "tokens" in c]
    assert len(token_chunks) >= 3
    first_t = next(t for t, c in chunks if "tokens" in c)
    done_t = chunks[-1][0]
    assert first_t < done_t


def test_streaming_oversized_prompt_clean_400(model_server):
    url, _, _ = model_server
    code, out = _post(f"{url}/generate",
                      {"tokens": list(range(99)), "max_new_tokens": 2,
                       "stream": True})
    assert code == 400 and "error" in out


class _FakeEngine:
    """Minimal engine double recording decode burst sizes."""

    def __init__(self, n_slots=4, fail_steps=0):
        self.n_slots = n_slots
        self.waiting = []
        self.slot_req = {}
        self.finished = []
        self.free_slots = list(range(n_slots))
        self.buckets = (16,)
        self.bursts = []
        self.fail_steps = fail_steps
        self._rid = 0
        self.reset_calls = 0

    def add_request(self, tokens, max_new):
        r = eng.Request(rid=self._rid, prompt=list(tokens),
                        max_new_tokens=max_new)
        self._rid += 1
        self.waiting.append(r)
        return r.rid

    def admit(self, on_wave=None):
        if self.fail_steps > 0:
            self.fail_steps -= 1
            raise RuntimeError("boom")
        while self.waiting and self.free_slots:
            r = self.waiting.pop(0)
            r.slot = self.free_slots.pop(0)
            r.tokens.append(7)
            import time as _t
            r.first_token_s = _t.time()
            self.slot_req[r.slot] = r
            if on_wave:
                on_wave()

    def decode_burst(self, max_burst=8):
        self.bursts.append(max_burst)
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


def test_adaptive_burst_short_while_slots_free():
    """Decode bursts stay short while free slots remain (a late arrival
    must not wait out a full max_burst decode before its prefill) and
    go long only once every slot is busy."""
    fake = _FakeEngine(n_slots=2)
    model = srv.ModelServer(fake, max_burst=16, open_burst=2)
    try:
        p1 = model._add([1, 2], 64)
        p2 = model._add([3], 64)      # fills both slots
        p3 = model._add([4], 4)       # waits -> slots stay full
        import time
        deadline = time.time() + 30
        while len(fake.bursts) < 3 and time.time() < deadline:
            time.sleep(0.01)
        assert fake.bursts, "no decode bursts ran"
        # Slots were full from the first decode on -> full bursts.
        assert fake.bursts[0] == 16
        p3.event.wait(timeout=30)
        del p1, p2
    finally:
        model.shutdown()


def test_adaptive_burst_open_while_slots_free():
    """With free slots remaining the server uses open_burst, whatever
    the clock says."""
    fake = _FakeEngine(n_slots=8)
    model = srv.ModelServer(fake, max_burst=16, open_burst=2)
    try:
        p = model._add([1, 2], 6)
        assert p.event.wait(timeout=30)
        assert fake.bursts and all(b == 2 for b in fake.bursts)
    finally:
        model.shutdown()


def test_adaptive_burst_short_after_a_quiet_spell():
    """A spell without arrivals must not lengthen the bursts while a
    slot is free: the next arrival would wait out the long burst in
    flight and the one queued behind it (the rule this replaces turned
    on a wall-clock second, and a replayed schedule's first-token tail
    forked on which side of it a burst was dispatched)."""
    fake = _FakeEngine(n_slots=8)
    model = srv.ModelServer(fake, max_burst=16, open_burst=2)
    burst = fake.decode_burst

    def burst_long_after_the_last_arrival(max_burst=8):
        model._last_arrival = -1e9
        return burst(max_burst)

    fake.decode_burst = burst_long_after_the_last_arrival
    try:
        p = model._add([1, 2], 6)
        assert p.event.wait(timeout=30)
        assert len(fake.bursts) >= 2 and all(b == 2 for b in fake.bursts)
    finally:
        model.shutdown()


def test_engine_failure_resets_and_recovers():
    """An engine exception fails in-flight requests AND resets the
    engine's queue/slot state so later requests succeed (advisor r3:
    stale waiting entries re-poisoned every subsequent step)."""
    fake = _FakeEngine(n_slots=2, fail_steps=1)
    model = srv.ModelServer(fake, max_burst=4, open_burst=4)
    try:
        p = model._add([1], 4)
        assert p.event.wait(timeout=30)
        assert "error" in (p.result or {})
        assert fake.reset_calls == 1
        assert model._ready.is_set()      # engine reset ok -> healthy
        p2 = model._add([2], 3)
        assert p2.event.wait(timeout=30)
        assert p2.result and "error" not in p2.result
    finally:
        model.shutdown()


def test_engine_reset_failure_flips_health():
    fake = _FakeEngine(n_slots=2, fail_steps=1)

    def bad_reset():
        raise RuntimeError("device gone")

    fake.reset = bad_reset
    model = srv.ModelServer(fake, max_burst=4)
    try:
        p = model._add([1], 4)
        assert p.event.wait(timeout=30)
        assert not model._ready.is_set()  # /health now 503
    finally:
        model.shutdown()


def test_engine_reset_clears_slots():
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    e = eng.InferenceEngine(params, cfg, n_slots=2, max_len=32,
                            prompt_buckets=(8,))
    e.add_request([1, 2, 3], max_new_tokens=64)   # stays active
    e.add_request([4, 5], max_new_tokens=64)
    e.add_request([6], max_new_tokens=2)          # queued (no slot)
    e.step()
    assert e.slot_req and e.waiting
    e.reset()
    assert not e.slot_req and not e.waiting and not e.finished
    assert sorted(e.free_slots) == [0, 1]
    assert int(e.cache["length"].sum()) == 0
    # The engine still serves fresh requests after a reset.
    out = e.generate([[9, 8]], max_new_tokens=3)
    assert len(out[0]) == 3


@pytest.fixture(scope="module")
def wave_engines():
    """An unpadded engine and a padded, warmed one with its compile
    watch armed (both built once: the cases below share the compiles)."""
    from skypilot_tpu.observability import flight as fl
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    plain = eng.InferenceEngine(params, cfg, n_slots=8, max_len=32,
                                prompt_buckets=(8,))
    padded = eng.InferenceEngine(params, cfg, n_slots=8, max_len=32,
                                 prompt_buckets=(8,), max_wave=4,
                                 pad_waves=True,
                                 flight_recorder=fl.FlightRecorder())
    assert padded.warm_programs(max_burst=8) > 0   # generate(): k <= 8
    padded.declare_warmup_complete()
    return plain, padded


@pytest.mark.parametrize("n_requests, padded_rows", [
    (1, [1]), (2, [4]), (3, [4]), (4, [4]), (5, [4, 1])])
def test_pad_waves_single_program_per_bucket(wave_engines, n_requests,
                                             padded_rows):
    """pad_waves pads an admission wave to the smallest rung of
    {1, max_wave} that holds it: results are identical to the unpadded
    engine, a lone request prefills one row, and after warm_programs no
    wave size meets a program the compile watch has not seen."""
    from skypilot_tpu.observability import metrics as metrics_lib
    plain, padded = wave_engines
    prompts = [[3, 1, 4], [1, 5], [9, 2, 6, 5], [3, 5, 8],
               [9, 7]][:n_requests]
    want = plain.generate(prompts, max_new_tokens=4)
    programs = padded.compile_watch.count
    seq0 = padded.flight.seq()

    def waves_counted():
        fam = metrics_lib.REGISTRY.snapshot().get(
            "skytpu_prefill_waves_total", {"samples": []})
        return {s["labels"]["rows"]: s["value"] for s in fam["samples"]
                if s["labels"]["bucket"] == "8"}
    before = waves_counted()
    got = padded.generate(prompts, max_new_tokens=4)
    assert got == want
    waves = [r for r in padded.flight.since(seq0) if r["burst"] == "wave"]
    assert [r["program"]["rows"] for r in waves] == padded_rows
    assert [len(r["rids"]) for r in waves] == [
        min(n_requests, 4), 1][:len(waves)]
    assert padded.compile_watch.count == programs
    assert padded.compile_watch.unexpected == []
    after = waves_counted()
    for rows in ("1", "4"):
        assert after.get(rows, 0) - before.get(rows, 0) == \
            padded_rows.count(int(rows))


def test_metrics_endpoint_exposition(model_server):
    """GET /metrics returns valid Prometheus text exposition carrying
    the serving histograms after at least one request (acceptance
    criterion of the observability PR)."""
    from skypilot_tpu.observability import metrics as metrics_lib

    url, _, _ = model_server
    code, _ = _post(f"{url}/generate",
                    {"tokens": [2, 7, 1], "max_new_tokens": 3})
    assert code == 200
    with urllib.request.urlopen(f"{url}/metrics", timeout=30) as r:
        assert r.status == 200
        assert r.headers.get("Content-Type") == metrics_lib.CONTENT_TYPE
        text = r.read().decode()
    fams = metrics_lib.parse_exposition(text)
    for name in ("skytpu_ttft_seconds", "skytpu_decode_step_seconds"):
        assert fams[name]["type"] == "histogram"
        count = sum(v for labels, v in fams[name]["samples"]
                    if labels.get("__name__") == f"{name}_count")
        assert count >= 1, name
    slots = fams["skytpu_slots_active"]
    assert slots["type"] == "gauge" and slots["samples"]
    # The gauge is process-global and other tests in this module build
    # their own engines, so assert a pool exists rather than its size.
    assert fams["skytpu_slots_total"]["samples"][0][1] >= 1
    # The HTTP layer observed itself too, labeled by route.
    http = fams["skytpu_http_requests_total"]
    assert any(labels.get("route") == "/generate" and v >= 1
               for labels, v in http["samples"])
    # Server wave-flush span double-records into its histogram.
    assert "skytpu_server_wave_flush_seconds" in fams
    # Unknown paths collapse into route="other": a scanner must not
    # mint unbounded label series in the process-global registry.
    try:
        urllib.request.urlopen(f"{url}/wp-login.php", timeout=30)
    except urllib.error.HTTPError as e:
        assert e.code == 404
    with urllib.request.urlopen(f"{url}/metrics", timeout=30) as r:
        fams2 = metrics_lib.parse_exposition(r.read().decode())
    routes = {labels.get("route")
              for labels, _ in fams2["skytpu_http_requests_total"]["samples"]}
    assert "other" in routes and "/wp-login.php" not in routes


def test_debug_flight_endpoint(model_server):
    """GET /debug/flight returns the engine's live burst ring + the
    compile-watch program registry (docs/observability.md §Flight
    recorder); ?n= caps the tail."""
    url, _, _ = model_server
    code, _ = _post(f"{url}/generate",
                    {"tokens": [4, 9, 2], "max_new_tokens": 3})
    assert code == 200
    with urllib.request.urlopen(f"{url}/debug/flight?n=5",
                                timeout=30) as r:
        assert r.status == 200
        payload = json.loads(r.read())
    assert payload["enabled"] is True
    assert payload["warm"] is False        # no --warm-grid here
    assert payload["unexpected"] == []
    assert 0 < len(payload["records"]) <= 5
    rec = payload["records"][-1]
    assert rec["kind"] == "flight"
    assert rec["burst"] in ("wave", "chunk", "decode", "verify",
                            "decode1")
    assert "layout" in rec["program"]
    # The program registry saw the engine's jit entry points compile.
    assert payload["programs"]
    assert any(k.startswith(("decode_burst", "admit_wave"))
               for k in payload["programs"])


def test_debug_flight_since_cursor(model_server):
    """?since=<seq> is the incremental tail (`skytpu flight --follow`):
    each response carries the ring's cursor, and re-sending it returns
    only records stamped after it."""
    url, _, _ = model_server
    with urllib.request.urlopen(f"{url}/debug/flight?n=1",
                                timeout=30) as r:
        first = json.loads(r.read())
    seq = first["seq"]
    assert seq > 0
    # Nothing new yet: the delta from the cursor is empty.
    with urllib.request.urlopen(f"{url}/debug/flight?since={seq}",
                                timeout=30) as r:
        delta = json.loads(r.read())
    assert delta["records"] == [] and delta["seq"] == seq
    # New traffic lands past the cursor — and only it.
    code, _ = _post(f"{url}/generate",
                    {"tokens": [7, 1, 5], "max_new_tokens": 2})
    assert code == 200
    with urllib.request.urlopen(f"{url}/debug/flight?since={seq}",
                                timeout=30) as r:
        delta = json.loads(r.read())
    assert delta["records"] and delta["seq"] > seq
    assert all(r["seq"] > seq for r in delta["records"])


def test_debug_forensics_endpoint(model_server):
    """GET /debug/forensics: the tail-detector state + exemplar index;
    ?rid= builds the request's critical-path ledger from the live ring
    (docs/observability.md §Request forensics)."""
    url, _, _ = model_server
    code, out = _post(f"{url}/generate",
                      {"tokens": [6, 2, 8], "max_new_tokens": 3})
    assert code == 200
    with urllib.request.urlopen(f"{url}/debug/forensics",
                                timeout=30) as r:
        payload = json.loads(r.read())
    assert payload["enabled"] is True
    assert set(payload["tail"]["estimates"]) == {"ttft", "tpot"}
    assert payload["tail"]["estimates"]["ttft"]["count"] >= 1
    # Find a retired rid in the ring and ask why it was slow.
    with urllib.request.urlopen(f"{url}/debug/flight?n=8192",
                                timeout=30) as r:
        records = json.loads(r.read())["records"]
    retires = [r for r in records if r["burst"] == "retire"]
    assert retires, "forensics-on server emitted no retire records"
    rid = retires[-1]["rids"][0]
    with urllib.request.urlopen(f"{url}/debug/forensics?rid={rid}",
                                timeout=30) as r:
        ans = json.loads(r.read())
    led = ans["ledger"]
    assert led["rid"] == rid
    total = sum(p["ms"] for p in led["phases"])
    assert total == pytest.approx(led["wall_ms"], abs=0.05)
    assert ans["records"]
    # Unknown rid -> typed 404; bad rid -> 400.
    try:
        urllib.request.urlopen(f"{url}/debug/forensics?rid=999999",
                               timeout=30)
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404 and "999999" in json.loads(e.read())["error"]
    try:
        urllib.request.urlopen(f"{url}/debug/forensics?rid=bogus",
                               timeout=30)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
