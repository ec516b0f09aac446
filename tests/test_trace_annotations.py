"""The program's own phases on the profiler's clock (ISSUE 25): count
invariants of the serve loop's and the engine's annotations under a live
``jax.profiler`` trace on a CPU engine, the trainer's ``train.*``
phases through ``GoodputRecorder.phase``, and the helper itself —
inert without a trace, importable without JAX (``python -S``).

The trace is read with the benchmark's own reduction
(``benchmarks/spans.py``): the same reader the per-layer metrics use.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from benchmarks import spans
from skypilot_tpu.infer import engine as eng
from skypilot_tpu.infer import server
from skypilot_tpu.models import llama
from skypilot_tpu.observability import goodput
from skypilot_tpu.utils import timeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# (prompt tokens, new tokens): 40 and 70 exceed the 32-token chunk and
# take the chunked path (two and three chunks); the rest ride waves.
REQUESTS = [(5, 14), (20, 12), (70, 4), (9, 7), (40, 3), (12, 1), (30, 8)]


def _trace(out_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)


def _annotations(out_dir):
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(out_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return spans.read_xspace(path)["annotations"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced stretch of a ModelServer over a tiny CPU engine,
    requests submitted from as many client threads."""
    cfg = llama.CONFIGS["llama3-tiny"]
    params = llama.init_params(jax.random.key(0), cfg)
    engine = eng.InferenceEngine(
        params, cfg, n_slots=4, max_len=128,
        prompt_buckets=(16, 32, 64, 128), prefill_chunk=32, kv_block=16,
        max_wave=2, pad_waves=True)
    ms = server.ModelServer(engine, max_burst=4, open_burst=2)
    assert ms._ready.wait(300)
    out = tmp_path_factory.mktemp("live_trace")
    decoded0 = eng.DECODE_TOKENS._require_default().value
    _trace(out)
    results = [None] * len(REQUESTS)

    def client(i, n, m):
        with timeline.phase("http.request", n=n):   # a handler's prefix
            results[i] = ms.submit(list(range(1, n + 1)), m)

    threads = [threading.Thread(target=client, args=(i, n, m))
               for i, (n, m) in enumerate(REQUESTS)]
    for t in threads:
        t.start()
        time.sleep(0.005)
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    time.sleep(0.35)      # one whole idle stretch (capped at 0.25 s)
    anns = _annotations(out)
    decoded = eng.DECODE_TOKENS._require_default().value - decoded0
    ms.shutdown()
    return anns, results, decoded


def _named(anns, name):
    return [a for a in anns if a[0] == name]


def test_every_request_was_served(served):
    _, results, _ = served
    assert [len(r["tokens"]) for r in results] == [m for _, m in REQUESTS]


def test_decode_fetch_tokens_equal_the_decode_counter(served):
    anns, _, decoded = served
    fetched = spans.sum_args(_named(anns, "engine.decode.fetch"), "tokens")
    assert fetched == decoded > 0
    retired = spans.sum_args(_named(anns, "engine.decode.fetch"), "retired")
    # (12, 1) retires at its first token, inside its wave's fetch
    assert retired <= len(_named(anns, "engine.retire")) == len(REQUESTS)


def test_wave_rows_and_first_chunks_equal_requests_admitted(served):
    anns, _, _ = served
    rows = spans.sum_args(_named(anns, "engine.wave.dispatch"), "rows")
    first_chunks = [a for a in _named(anns, "engine.chunk.dispatch")
                    if "queue_ms" in a[4]]
    assert rows + len(first_chunks) == len(REQUESTS)
    assert len(first_chunks) == sum(1 for n, _ in REQUESTS if n > 32)
    chunks = _named(anns, "engine.chunk.dispatch")
    assert len(chunks) == 3 + 2                  # 70 -> 3, 40 -> 2 chunks
    assert spans.sum_args(chunks, "chunk_tokens") == 70 + 40
    assert all(a[4]["padded_tokens"] == 32 for a in chunks)
    assert spans.sum_args(chunks, "final") == 2
    waves = _named(anns, "engine.wave.dispatch")
    assert spans.sum_args(waves, "prompt_tokens") == sum(
        n for n, _ in REQUESTS if n <= 32)
    for a in waves:
        # a wave is padded to a rung of {1, max_wave}
        assert a[4]["padded_rows"] in (1, 2)
        assert a[4]["rows"] <= a[4]["padded_rows"]
        assert a[4]["prompt_tokens"] <= a[4]["rows"] * a[4]["bucket"]


def test_only_a_final_chunk_is_fetched_the_others_are_queued(served):
    """A non-final chunk says ``queued`` on its dispatch, has a
    ``land`` and no ``fetch``; the trailers still count every chunk."""
    anns, results, _ = served
    chunks = _named(anns, "engine.chunk.dispatch")
    assert [a[4]["queued"] for a in chunks] == \
        [1 - a[4]["final"] for a in chunks]
    fetches = _named(anns, "engine.chunk.fetch")
    assert len(fetches) == 2 and all(a[4]["final"] == 1 for a in fetches)
    assert len(_named(anns, "engine.chunk.land")) == len(chunks) - 2
    assert sorted(r["prefill_chunks"] for r in results) == \
        [0] * 5 + [2, 3]


def test_queue_wait_never_exceeds_time_to_first_token(served):
    anns, _, _ = served
    first = 0
    for a in _named(anns, "engine.wave.fetch"):
        assert 0 <= a[4]["queue_ms_sum"] <= a[4]["ttft_ms_sum"]
        first += a[4]["first_tokens"]
    finals = [a for a in _named(anns, "engine.chunk.fetch")
              if "ttft_ms" in a[4]]
    for a in finals:
        assert a[4]["final"] == 1
        assert 0 <= a[4]["queue_ms"] <= a[4]["ttft_ms"]
    assert first + len(finals) == len(REQUESTS)
    # the first chunk's dispatch and the final chunk's fetch report the
    # same queue wait of the same request
    queued = sorted(a[4]["queue_ms"]
                    for a in _named(anns, "engine.chunk.dispatch")
                    if "queue_ms" in a[4])
    assert queued == sorted(a[4]["queue_ms"] for a in finals)


def test_decode_dispatches_pair_with_their_fetches_by_seq(served):
    anns, _, _ = served
    fetches = {a[4]["seq"]: a[4]
               for a in _named(anns, "engine.decode.fetch")}
    seen = {}
    for a in _named(anns, "engine.decode.dispatch"):
        args = a[4]
        assert args["rows"] == 4 + 1 and 1 <= args["slots"] <= 4
        assert args["why"] in ("open", "full", "chunking")
        assert args["k"] in (1, 2, 4)
        seen[args["seq"]] = seen.get(args["seq"], 0) + 1
    assert set(seen) == set(fetches)
    for seq, n in seen.items():
        assert fetches[seq]["parts"] == n
    # a burst dispatched while a chunked prefill was queued says so
    assert any(a[4]["why"] == "chunking"
               for a in _named(anns, "engine.decode.dispatch"))


def test_a_decode_round_is_one_program(served):
    anns, _, _ = served
    fetches = _named(anns, "engine.decode.fetch")
    assert fetches and all(a[4]["parts"] == 1 for a in fetches)
    seqs = [a[4]["seq"] for a in _named(anns, "engine.decode.dispatch")]
    assert sorted(seqs) == sorted(a[4]["seq"] for a in fetches)
    assert len(set(seqs)) == len(seqs)


def test_promoted_counts_the_slots_above_their_own_rung(served):
    anns, _, _ = served
    dispatches = _named(anns, "engine.decode.dispatch")
    for a in dispatches:
        assert 0 <= a[4]["promoted"] < a[4]["slots"]
        assert a[4]["span"] in (16, 32, 64, 128)
    # prompts of 5 to 70 tokens decode side by side on a ladder of
    # 16 / 32 / 64 / 128 rows: some round mixes rungs
    assert any(a[4]["promoted"] > 0 for a in dispatches)
    # a slot alone in its round rides its own rung
    assert all(a[4]["promoted"] == 0 for a in dispatches
               if a[4]["slots"] == 1)


def test_tiles_counts_the_turns_of_the_live_rows(served):
    """``tiles`` is host arithmetic over ``slots``: the turns of
    ``kvcache.TILE`` slots a layer takes to read and attend them."""
    from skypilot_tpu.infer import kvcache
    anns, _, _ = served
    dispatches = _named(anns, "engine.decode.dispatch")
    assert dispatches
    for a in dispatches:
        assert a[4]["tiles"] == -(-a[4]["slots"] // kvcache.TILE) == 1


def test_loop_annotations_come_from_one_thread(served):
    anns, _, _ = served
    loop = {a[3] for a in anns if a[0].startswith(("server.", "engine."))}
    assert len(loop) == 1
    assert _named(anns, "server.idle") and _named(anns, "server.inbox")
    assert spans.sum_args(_named(anns, "server.inbox"), "n") \
        == len(REQUESTS)
    assert spans.sum_args(_named(anns, "server.results"), "n") \
        == len(REQUESTS)
    # the client threads' own spans take another prefix, which the
    # benchmark's idle-gap labels and its span reader both ignore
    from benchmarks import trace
    assert not "http.request".startswith(
        trace.ANNOTATION_PREFIXES + spans.PREFIXES)


def test_names_differ_from_the_benchmarks_outside_wrappers(served):
    anns, _, _ = served
    wrapped = {"server._step", "server._drain_inbox",
               "server._flush_streams", "server._complete_burst",
               "engine.step"}
    assert not wrapped & {a[0] for a in anns}


def test_goodput_phase_yields_the_train_annotations(tmp_path):
    gp = goodput.GoodputRecorder(param_count=0)
    _trace(tmp_path)
    for step in range(3):
        gp.step_start(step)
        with gp.phase("data_wait"):
            pass
        with gp.phase("compute", tokens=4096):
            pass
        with gp.phase("eval"):
            with timeline.phase("train.loss_fetch"):
                pass
        with gp.phase("ckpt_save"):
            pass
        gp.step_end(tokens=4096)
    anns = _annotations(tmp_path)
    steps = _named(anns, "train.step")
    assert [a[4]["step_num"] for a in steps] == [0, 1, 2]
    assert all(a[4]["tokens"] == 4096 and a[4]["_r"] == 1 for a in steps)
    for name in ("train.data_wait", "train.eval", "train.loss_fetch",
                 "train.save"):
        assert len(_named(anns, name)) == 3, name
    # the ledger entry and the annotation come from the one ``with``
    assert gp.snapshot()["steps"] == 3


def test_phase_is_inert_without_a_trace_and_feeds_the_chrome_file(
        tmp_path, monkeypatch):
    monkeypatch.delenv(timeline.ENV_VAR, raising=False)
    before = len(timeline._events)
    with timeline.phase("engine.decode.dispatch", k=4) as ph:
        ph.set(tokens=3)
    assert len(timeline._events) == before
    path = tmp_path / "timeline.json"
    monkeypatch.setenv(timeline.ENV_VAR, str(path))
    with timeline.phase("engine.decode.fetch", k=4) as ph:
        ph.set(tokens=3)
    timeline.save_now()
    events = json.load(open(path))["traceEvents"]
    mine = [e for e in events if e["name"] == "engine.decode.fetch"]
    assert mine and mine[-1]["args"] == {"k": 4, "tokens": 3}
    assert mine[-1]["ph"] == "X" and mine[-1]["dur"] >= 0


def test_timeline_imports_and_phases_without_jax_under_python_S():
    code = ("import sys; sys.path.insert(0, %r); "
            "from skypilot_tpu.utils import timeline\n"
            "with timeline.phase('server.idle', n=1) as p: p.set(m=2)\n"
            "assert 'jax' not in sys.modules; print('ok')" % ROOT)
    done = subprocess.run([sys.executable, "-S", "-c", code],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=60)
    assert done.returncode == 0, done.stderr.decode()[-500:]
    assert done.stdout.decode().strip() == "ok"


def test_profiler_is_touched_in_one_helper_and_two_entry_points():
    hits = []
    for dirpath, _, files in os.walk(os.path.join(ROOT, "skypilot_tpu")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    for line in fh:
                        if "jax.profiler" in line:
                            hits.append(os.path.relpath(path, ROOT))
    assert sorted(set(hits)) == ["skypilot_tpu/infer/server.py",
                                 "skypilot_tpu/train/run.py",
                                 "skypilot_tpu/utils/timeline.py"]
    assert hits.count("skypilot_tpu/infer/server.py") == 1
    assert hits.count("skypilot_tpu/train/run.py") == 1
