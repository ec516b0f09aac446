"""``--rehearse --trace 1`` of each cell on the CPU: the program's own
annotations are in the profiler's trace the harness already takes, with
their arguments, and all of them come from the serve loop's one thread.
(The QLoRA cell's loop is the benchmark's own, ``train_child.py``: it
reaches no ``train.`` annotation, which live in ``train/run.py``.)"""

import json

import pytest

from benchmarks import spans
from test_run_serve import bench, last_line

# What the chat cell's rehearsal (4 slots, prompts of 4-96 tokens, all
# below the chunk threshold) can reach of ISSUE 25's table, with the
# arguments each must carry.
REACHED = {
    "server.idle": set(),
    "server.coalesce": {"waiting"},
    "server.inbox": {"n"},
    "server.streams": {"n", "tokens"},
    "server.results": {"n"},
    "engine.admit.plan": {"n", "held", "stalled"},
    "engine.wave.dispatch": {"rows", "padded_rows", "bucket",
                             "prompt_tokens", "queue_ms_sum",
                             "queue_ms_max"},
    "engine.wave.fetch": {"rows", "first_tokens", "queue_ms_sum",
                          "ttft_ms_sum"},
    "engine.decode.dispatch": {"seq", "k", "slots", "rows", "span", "why",
                               "waiting"},
    "engine.decode.fetch": {"seq", "k", "parts", "tokens", "retired",
                            "waiting"},
    "engine.decode.commit": set(),
    "engine.retire": {"prompt_tokens", "tokens"},
}


@pytest.fixture(scope="module")
def chat(tmp_path_factory):
    out = tmp_path_factory.mktemp("traced_chat")
    rc, lines, err = bench(["--workload", "mistral-7b-w8a8.chat-steady",
                            "--seed", str(2 ** 31 + 29), "--seconds", "6",
                            "--trace", "1", "--rehearse",
                            "--out", str(out)])
    assert rc == 0, err[-2000:]
    results = json.load(open(out / "results.json"))
    return last_line(lines), spans.reduce_xplane(
        results["facts"]["trace"]["file"])


def test_traced_chat_rehearsal_still_prints_no_device_metric(chat):
    line, red = chat
    assert line["metrics"] == {} and line["correct"] is True
    assert red["platform"] == "cpu" and red["modules"] == {}


@pytest.mark.parametrize("name", sorted(REACHED))
def test_chat_rehearsal_reaches_the_annotation_with_its_arguments(
        chat, name):
    _, red = chat
    events = [a for a in red["annotations"] if a[0] == name]
    assert events, f"no {name} in the traced stretch"
    for a in events:
        assert REACHED[name] <= set(a[4]), (name, a[4])


def test_chat_rehearsal_counts_are_consistent(chat):
    _, red = chat
    anns = red["annotations"]
    # one thread: a handler-thread span under these prefixes would
    # corrupt the nesting trace.py's idle-gap labels rely on
    assert red["host_lines"] == 1
    for a in anns:
        if a[0] == "engine.wave.dispatch":
            assert 1 <= a[4]["rows"] <= a[4]["padded_rows"]
            assert a[4]["prompt_tokens"] <= a[4]["rows"] * a[4]["bucket"]
            assert a[4]["queue_ms_max"] <= a[4]["queue_ms_sum"] + 1e-6
        if a[0] == "engine.wave.fetch":
            assert a[4]["queue_ms_sum"] <= a[4]["ttft_ms_sum"]
        if a[0] == "engine.decode.dispatch":
            assert 1 <= a[4]["slots"] < a[4]["rows"]
            assert a[4]["why"] in ("open", "quiet", "full", "chunking")
    fetched = {a[4]["seq"]: a[4] for a in anns
               if a[0] == "engine.decode.fetch"}
    for a in anns:
        f = fetched.get(a[4].get("seq")) \
            if a[0] == "engine.decode.dispatch" else None
        if f is not None:
            assert f["k"] == a[4]["k"]
            assert f["tokens"] <= f["k"] * a[4]["rows"] * f["parts"]


def test_traced_qlora_rehearsal_reduces_to_no_program_annotation(tmp_path):
    rc, lines, err = bench(["--workload", "mistral-7b-qlora.sft-2k",
                            "--seed", "8", "--seconds", "3", "--trace", "1",
                            "--rehearse", "--out", str(tmp_path / "out")])
    assert rc == 0, err[-2000:]
    line = last_line(lines)
    assert line["metrics"] == {} and line["correct"] is True
    reh = [l for l in lines if l.startswith("REHEARSAL_TRACE ")]
    assert reh and "jit_step" in reh[0]
    results = json.load(open(tmp_path / "out" / "results.json"))
    red = spans.reduce_xplane(results["facts"]["trace"]["file"])
    assert red["platform"] == "cpu" and red["annotations"] == []
    assert red["decode"]["bursts"] == 0
