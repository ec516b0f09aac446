"""The start-up metrics (ISSUE 40): reader ``startup_event`` over canned
child logs — the values, the cut at ``server.listening`` / at the
window's ``start_wall``, nothing off the chip, nothing from a program
that echoes no such line — and the manifest as it has grown."""

import json

import pytest

from benchmarks import manifest
from benchmarks.run import read_metrics

LAYER = ("Start-up and compile (infer/server.py, infer/engine.py, "
         "train/trainer.py, observability/flight.py)")
CHAT = "mistral-7b-w8a8.chat-steady"
GLM = "glm-4.7-flash-bf16.longprompt-steady"
QLORA = "mistral-7b-qlora.sft-2k"
FOUR = "internlm2-1.8b.pretrain-4chip"
OLMO = "olmo-hybrid-7b-bf16.longprompt-steady"
# name -> (unit, source, the cells that print it)
NEW = {
    "setup_pre_program_s": ("s", "program_span", [CHAT, GLM, QLORA, FOUR]),
    "setup_weights_state_s": ("s", "program_span",
                              [CHAT, GLM, QLORA, FOUR]),
    "setup_warm_grid_s": ("s", "program_span", [CHAT, GLM]),
    "setup_lowering_s": ("s", "program_counter", [CHAT, GLM, QLORA, FOUR]),
    "setup_compile_or_load_s": ("s", "program_counter",
                                [CHAT, GLM, QLORA, FOUR]),
    "setup_cache_misses": ("programs", "program_counter",
                           [CHAT, GLM, QLORA, FOUR])}
ON_CHIP = {"trace": {"platform": "tpu"}}


def event(name, ts, **attrs):
    return json.dumps({"kind": "event", "name": name, "ts_s": ts,
                       "pid": 1, "tid": 1, "proc": "p", "attrs": attrs})


def phase(ts, name, s):
    return event("startup.phase", ts, phase=name, s=s)


def compiled(ts, fun, trace, lower, comp, load, hit):
    return event("program.compiled", ts, fun_name=fun, trace_s=trace,
                 lower_s=lower, compile_s=comp, load_s=load, cache_hit=hit)


SERVER_LOG = "\n".join([
    "BENCH_DEVICE {\"platform\": \"tpu\"}",
    phase(100.0, "before_main", 14.5),
    phase(100.1, "imports", 0.01),
    phase(100.2, "backend", 0.02),
    "some library's warning {not json",
    phase(102.0, "weights", 1.75),
    compiled(102.5, "jit(broadcast_in_dim)", 0.01, 0.02, 0.25, 0.0, None),
    phase(103.0, "engine_init", 1.0),
    compiled(110.0, "jit(_decode_burst)", 0.5, 0.75, 0.125, 0.25, True),
    compiled(120.0, "jit(_prefill_chunk)", 0.25, 0.5, 8.0, 0.0, False),
    phase(120.5, "warm_grid.decode", 17.0),
    '{"kind": "event", "name": "program.compiled", "ts_s": torn',
    phase(133.0, "warm_grid", 30.0),
    phase(133.2, "gc_freeze", 0.2),
    phase(133.3, "listen", 0.1),
    event("server.listening", 133.4, port=1, startup={"total_s": 48.0}),
    # after the server said it listens: the traffic's, not start-up's
    compiled(140.0, "jit(_decode_burst)", 9.0, 9.0, 9.0, 9.0, False),
    phase(141.0, "weights", 9.0),
    event("engine.unexpected_compile", 140.0, program="x"),
]) + "\n"

TRAIN_LOG = "\n".join([
    phase(200.0, "before_main", 11.25),
    compiled(200.5, "jit(init_fn)", 0.125, 0.25, 0.0, 0.5, True),
    phase(201.0, "state", 0.875),
    compiled(205.0, "jit(step)", 0.5, 0.375, 0.0, 0.625, True),
    "BENCH_WINDOW {\"start_wall\": 210.0}",
    # the reference compiles after the window: not start-up
    compiled(260.0, "jit(reference)", 5.0, 5.0, 5.0, 0.0, False),
    phase(261.0, "state", 5.0),
    "BENCH_RESULT {\"values\": {}}",
]) + "\n"

# reading -> (server.log's value, train.log's value)
WANT = {
    "setup_pre_program_s": (14.5, 11.25),
    "setup_weights_state_s": (1.75, 0.875),
    "setup_warm_grid_s": (30.0, None),
    "setup_lowering_s": (0.01 + 0.02 + 0.5 + 0.75 + 0.25 + 0.5,
                         0.125 + 0.25 + 0.5 + 0.375),
    "setup_compile_or_load_s": (0.25 + 0.125 + 0.25 + 8.0, 0.5 + 0.625),
    "setup_cache_misses": (1, 0)}


@pytest.fixture(scope="module")
def reader():
    return manifest.load_module("readers", "startup_event",
                                manifest.BENCH_DIR)


def args_of(name):
    spec = manifest.load_metric(name)
    assert spec["reader"] == "startup_event"
    return spec["args"]


def out_dir(tmp_path, log_name, text):
    (tmp_path / log_name).write_text(text)
    return {"out_dir": str(tmp_path)}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reading_of_a_served_start_stops_at_listening(reader, tmp_path,
                                                      name):
    ctx = out_dir(tmp_path, "server.log", SERVER_LOG)
    got = reader.read(ON_CHIP, ctx, **args_of(name))
    assert got == pytest.approx(WANT[name][0])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reading_of_a_training_start_stops_at_the_window(reader, tmp_path,
                                                         name):
    ctx = out_dir(tmp_path, "train.log", TRAIN_LOG)
    got = reader.read(ON_CHIP, ctx, **args_of(name))
    want = WANT[name][1]
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("facts", [
    {}, {"trace": None}, {"trace": {"platform": "cpu"}},
    {"device": {"platform": "tpu"}, "trace": {"platform": "cpu"}}])
def test_nothing_off_the_chip(reader, tmp_path, facts):
    ctx = out_dir(tmp_path, "server.log", SERVER_LOG)
    for name in NEW:
        assert reader.read(facts, ctx, **args_of(name)) is None


def test_nothing_from_a_program_that_echoes_no_such_line(reader, tmp_path):
    """The parent commit: ``server.listening`` without ``startup``, no
    ``startup.phase``, no ``program.compiled``. The reader returns
    nothing and does not raise; the line leaves the metric out."""
    parents = "\n".join([
        event("server.programs_warmed", 130.0, programs=30, warm_s=30.1),
        event("server.listening", 133.4, port=1)]) + "\n"
    ctx = out_dir(tmp_path, "server.log", parents)
    for name in NEW:
        assert reader.read(ON_CHIP, ctx, **args_of(name)) is None
    empty = tmp_path / "empty"                         # no log at all
    empty.mkdir()
    for name in NEW:
        assert reader.read(ON_CHIP, {"out_dir": str(empty)},
                           **args_of(name)) is None


def test_nothing_without_the_moment_the_window_began(reader, tmp_path):
    never_listened = SERVER_LOG.split(
        '{"kind": "event", "name": "server.listening"')[0]
    ctx = out_dir(tmp_path, "server.log", never_listened)
    assert reader.read(ON_CHIP, ctx, phases=["before_main"]) is None
    no_window = TRAIN_LOG.replace("BENCH_WINDOW", "BENCH_OTHER")
    ctx = out_dir(tmp_path, "train.log", no_window)
    (tmp_path / "server.log").unlink()
    assert reader.read(ON_CHIP, ctx, phases=["before_main"]) is None


def test_the_runner_prints_them_through_the_manifest(tmp_path):
    """``run.read_metrics`` over the grown manifest, as a traced run on
    the chip would: the chat cell's line gains all six."""
    spec = manifest.load_manifest()
    wanted = [m for m in manifest.cell_metrics(spec, CHAT, "per_layer")
              if m["name"] in NEW]
    assert [m["name"] for m in wanted] == list(NEW)
    ctx = dict(out_dir(tmp_path, "server.log", SERVER_LOG), config={})
    line = read_metrics(ctx, {"facts": ON_CHIP}, wanted,
                        manifest.BENCH_DIR)
    assert {k: v["value"] for k, v in line.items()} == pytest.approx(
        {k: v[0] for k, v in WANT.items()})
    assert line["setup_cache_misses"]["unit"] == "programs"


def test_the_grown_manifest_is_valid_and_only_grew():
    spec = manifest.load_manifest()
    manifest.validate(spec)
    tail = spec["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)     # appended, in order
    for m in tail:
        unit, source, cells = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": LAYER, "moves": "setup_s",
                     "workloads": cells}
        # ``setup_s`` lists no cells, so an entry without its own list
        # would fall to every cell, the hybrid cell's pinned set included
        assert OLMO not in m["workloads"]
        on_file = manifest.load_metric(m["name"])
        assert on_file["layer"] == LAYER and on_file["unit"] == unit
        assert on_file["moves"] == "setup_s"
    assert len(spec["per_layer"]) == 28 + len(NEW)
    assert len(spec["workloads"]) == 5 and len(spec["configs"]) == 5


@pytest.mark.parametrize("cell", [CHAT, GLM, QLORA, FOUR, OLMO])
def test_each_cell_joins_the_metrics_that_list_it(cell):
    spec = manifest.load_manifest()
    got = {m["name"] for m in manifest.cell_metrics(spec, cell, "per_layer")
           if m["name"] in NEW}
    assert got == {n for n, (_, _, cells) in NEW.items() if cell in cells}
