"""Tier-1 gate for the static-analysis suite (`skytpu lint`).

Three layers:

1. The whole tree must run clean against the checked-in baseline
   (``lint_baseline.json``) — no new findings, no rotted (stale)
   entries, every entry justified. This is the standing correctness
   gate the framework exists for.
2. Golden fixtures per checker: a ``*_bad.py`` file with seeded
   violations marked ``# expect: <rule>`` must be reported at exactly
   those lines with exactly those rules (nothing more), and its
   ``*_clean.py`` twin must pass.
3. Framework mechanics: per-file cache hit/invalidation (mtime AND
   content), checker-version invalidation, ``--baseline-update``
   round-trip, stale detection, partial (``--changed``) semantics.
"""

import json
import os
import re
import time

import pytest

from skypilot_tpu import analysis
from skypilot_tpu.analysis import baseline as baseline_lib
from skypilot_tpu.analysis import core as analysis_core
from skypilot_tpu.analysis.core import FileContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")

_EXPECT_RE = re.compile(r"#\s*expect:\s*([\w-]+(?:\s*,\s*[\w-]+)*)")


@pytest.fixture(autouse=True)
def _isolated_home(tmp_path, monkeypatch):
    """The cache must never write to the real user home from tests."""
    monkeypatch.setenv("SKYPILOT_TPU_HOME", str(tmp_path / "home"))


# ---------------------------------------------------------------------------
# 1. The gate: the tree is clean against the baseline.

def test_tree_clean_against_baseline():
    res = analysis.run(root=REPO, use_cache=False)
    msg = []
    for f in res.new:
        msg.append(f.format())
    for k in res.stale:
        msg.append(f"stale baseline entry (remove it): {k}")
    for k in res.unjustified:
        msg.append(f"baseline entry lacks a justification: {k}")
    assert res.clean, (
        "`skytpu lint` is not clean — fix the finding or (for a "
        "genuinely intentional case) baseline it WITH a one-line "
        "justification:\n  " + "\n  ".join(msg))
    # The suite saw the real tree: a scan refactor that silently
    # found nothing would otherwise pass vacuously.
    assert res.files_scanned > 100
    assert len(res.findings) >= 20, (
        "the checked-in baseline grandfathers ~30 findings; seeing "
        f"only {len(res.findings)} means a checker stopped scanning")


def test_baseline_entries_all_justified():
    base = baseline_lib.load(baseline_lib.default_path(REPO))
    assert base, "checked-in baseline missing"
    bad = [k for k, e in base.items()
           if not e["justification"].strip()
           or e["justification"].startswith("TODO")]
    assert not bad, f"baseline entries without justification: {bad}"


# ---------------------------------------------------------------------------
# 2. Golden fixtures.

def _fixture_ctx(name, rel):
    path = os.path.join(FIXTURES, name)
    return FileContext(path, rel)


def _expected(ctx):
    out = {}
    for i, line in enumerate(ctx.lines, start=1):
        m = _EXPECT_RE.search(line)
        if m:
            out[i] = sorted(r.strip() for r in m.group(1).split(","))
    return out


def _run_fixture(checker_name, name, rel, root=None):
    checker = analysis_core.get_checker(checker_name)
    ctx = _fixture_ctx(name, rel)
    if checker.scope == "file":
        findings = checker.check_file(ctx)
    else:
        findings = checker.check_project([ctx], root or REPO)
    return ctx, [f for f in findings if f.path == ctx.rel]


def _assert_golden(checker_name, name, rel, root=None):
    ctx, findings = _run_fixture(checker_name, name, rel, root)
    expected = _expected(ctx)
    got = {}
    for f in findings:
        got.setdefault(f.line, []).append(f.rule)
    got = {line: sorted(rules) for line, rules in got.items()}
    assert got == expected, (
        f"{name}: findings (line->rules) {got} != expected markers "
        f"{expected}")
    # Sanity: a fixture without seeded violations tests nothing.
    assert expected, f"{name} has no # expect: markers"


# (checker, bad fixture, clean twin, rel path that puts it in scope)
_GOLDEN = [
    ("retrace-safety", "retrace_bad.py", "retrace_clean.py",
     "skypilot_tpu/infer/fixture_retrace.py"),
    # Paged-KV shape: the block-gather attention pattern (PR 7) —
    # proves the checker covers table gathers/scatters, not just the
    # contiguous idiom.
    ("retrace-safety", "retrace_paged_bad.py", "retrace_paged_clean.py",
     "skypilot_tpu/infer/fixture_retrace_paged.py"),
    # Program-family shape (PR 28): serve programs reached through the
    # ``programs_for`` handle — the latent cache and the dropless
    # expert layer (retrace v6).
    ("retrace-safety", "retrace_family_bad.py",
     "retrace_family_clean.py",
     "skypilot_tpu/infer/fixture_retrace_family.py"),
    ("host-sync", "host_sync_bad.py", "host_sync_clean.py",
     "skypilot_tpu/infer/engine.py"),
    ("host-sync", "host_sync_paged_bad.py", "host_sync_paged_clean.py",
     "skypilot_tpu/infer/engine.py"),
    # Speculative-decode shape (PR 8): the K-position verify program
    # and the draft/accept hot path are guarded like the paged gather.
    ("retrace-safety", "retrace_spec_bad.py", "retrace_spec_clean.py",
     "skypilot_tpu/infer/fixture_retrace_spec.py"),
    ("host-sync", "host_sync_spec_bad.py", "host_sync_spec_clean.py",
     "skypilot_tpu/infer/engine.py"),
    # Draft-model speculation + async pipeline (PR 14): the drafter's
    # jitted rollout/lockstep-sync shape and the DraftEngine hot path
    # (infer/draft.py scope) are guarded like the verify shape.
    ("retrace-safety", "retrace_draft_bad.py",
     "retrace_draft_clean.py",
     "skypilot_tpu/infer/fixture_retrace_draft.py"),
    ("host-sync", "host_sync_draft_bad.py",
     "host_sync_draft_clean.py",
     "skypilot_tpu/infer/draft.py"),
    # Span-bucketed attention (PR 9): the static-span gather and the
    # host-side bucket/headroom selection are guarded like the paged
    # and spec shapes before them.
    ("retrace-safety", "retrace_span_bad.py", "retrace_span_clean.py",
     "skypilot_tpu/infer/fixture_retrace_span.py"),
    ("host-sync", "host_sync_span_bad.py", "host_sync_span_clean.py",
     "skypilot_tpu/infer/engine.py"),
    # Flight recorder (PR 10): burst records and the compile-watch
    # wrapper are host-only — a fetch on the record path stalls the
    # pipeline the recorder observes.
    ("host-sync", "host_sync_flight_bad.py",
     "host_sync_flight_clean.py",
     "skypilot_tpu/observability/flight.py"),
    # Multi-tenant QoS (PR 11): the DRR reorder / admission check run
    # per admission pass / per HTTP request — pure host bookkeeping;
    # a device fetch to rank tenants stalls the admission pipeline.
    ("host-sync", "host_sync_qos_bad.py", "host_sync_qos_clean.py",
     "skypilot_tpu/infer/qos.py"),
    # Paged-attention kernel (PR 12): Pallas kernel bodies are
    # reachable through their functools.partial wrappers (the
    # pallas_call idiom; retrace v3) and the per-tenant KV quota /
    # charge bookkeeping joined the host-sync engine scope (v7).
    ("retrace-safety", "retrace_kernel_bad.py",
     "retrace_kernel_clean.py",
     "skypilot_tpu/infer/fixture_retrace_kernel.py"),
    ("host-sync", "host_sync_kernel_bad.py",
     "host_sync_kernel_clean.py",
     "skypilot_tpu/infer/engine.py"),
    # Multi-LoRA adapter catalog (PR 13): the per-slot (A, B) gather
    # is guarded like the paged/span/spec shapes (adapter identity
    # must stay device DATA — concretizing it bakes one fine-tune
    # into the program), and the catalog claim/retire bookkeeping
    # joined the host-sync engine scope (v8).
    ("retrace-safety", "retrace_adapter_bad.py",
     "retrace_adapter_clean.py",
     "skypilot_tpu/infer/fixture_retrace_adapter.py"),
    ("host-sync", "host_sync_adapter_bad.py",
     "host_sync_adapter_clean.py",
     "skypilot_tpu/infer/engine.py"),
    # Device-truth attribution (PR 16): the calibrator tick/estimate
    # path, the HBM ledger and the roofline cost model ride every
    # dispatch / flight record — host-only by design, the sampled
    # calibration bracket being the one baselined sync (v10).
    ("host-sync", "host_sync_attr_bad.py",
     "host_sync_attr_clean.py",
     "skypilot_tpu/observability/attribution.py"),
    # Training goodput (PR 18): step_start/step_end bracket every
    # train step and the anomaly watchdog rides the loop's own loss
    # fetch — wall clocks and host dicts only; a device fetch inside
    # the ledger stalls the step it is measuring (v12).
    ("host-sync", "host_sync_goodput_bad.py",
     "host_sync_goodput_clean.py",
     "skypilot_tpu/observability/goodput.py"),
    ("lock-discipline", "locks_bad.py", "locks_clean.py",
     "skypilot_tpu/utils/fixture_locks.py"),
    ("typed-errors", "typed_errors_bad.py", "typed_errors_clean.py",
     "skypilot_tpu/server/fixture_typed.py"),
    ("bare-print", "bare_print_bad.py", "bare_print_clean.py",
     "skypilot_tpu/runtime/fixture_print.py"),
    ("adhoc-retry", "adhoc_retry_bad.py", "adhoc_retry_clean.py",
     "skypilot_tpu/fixture_retry.py"),
]


@pytest.mark.parametrize("checker,bad,clean,rel", _GOLDEN,
                         ids=[g[0] for g in _GOLDEN])
def test_golden_fixture(checker, bad, clean, rel):
    _assert_golden(checker, bad, rel)
    _, clean_findings = _run_fixture(checker, clean, rel)
    assert not clean_findings, (
        f"{clean}: clean twin produced findings: "
        f"{[f.format() for f in clean_findings]}")


def test_golden_metric_catalog(tmp_path):
    """Project-scope: needs a synthetic docs catalog at the root."""
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "| skytpu_documented_total | ... |\n"
        "| skytpu_documented_seconds | ... |\n"
        "| skytpu_fleet_scrape_up | ... |\n"
        "| skytpu_fleet_merge_errors | ... |\n")
    rel = "skypilot_tpu/observability/fixture_metrics.py"
    _assert_golden("metric-catalog", "metric_catalog_bad.py", rel,
                   root=str(tmp_path))
    _, clean_findings = _run_fixture(
        "metric-catalog", "metric_catalog_clean.py", rel,
        root=str(tmp_path))
    assert not clean_findings, [f.format() for f in clean_findings]


def test_retrace_unreachable_function_not_flagged():
    """`never_jitted` concretizes freely: no root reaches it."""
    ctx, findings = _run_fixture(
        "retrace-safety", "retrace_bad.py",
        "skypilot_tpu/infer/fixture_retrace.py")
    lines_with = [f.line for f in findings]
    src_line = next(i for i, l in enumerate(ctx.lines, 1)
                    if "never_jitted" in l)
    assert all(ln <= src_line for ln in lines_with)


def test_host_sync_out_of_scope_method_not_flagged():
    _, findings = _run_fixture("host-sync", "host_sync_bad.py",
                               "skypilot_tpu/infer/engine.py")
    assert not any("unscoped_helper" in f.ident for f in findings)


def test_bare_print_out_of_scope_dir():
    """The same file outside the daemon dirs produces nothing."""
    checker = analysis_core.get_checker("bare-print")
    ctx = _fixture_ctx("bare_print_bad.py",
                       "skypilot_tpu/client/fixture_print.py")
    assert checker.check_file(ctx) == []


# ---------------------------------------------------------------------------
# 3. Framework mechanics on a synthetic mini-tree.

def _mini_tree(tmp_path):
    root = tmp_path / "repo"
    pkg = root / "skypilot_tpu" / "runtime"
    pkg.mkdir(parents=True)
    mod = pkg / "daemon.py"
    mod.write_text('def tick():\n    print("hi")\n')
    return str(root), str(mod)


def _run_mini(root, **kw):
    return analysis.run(root=root, checkers=["bare-print"], **kw)


# Cache tests run the FULL suite (a checker subset deliberately never
# touches the cache — see test_checker_subset_run_never_touches_cache).

def _prints(res):
    return [f for f in res.findings if f.checker == "bare-print"]


def test_cache_hit_and_content_invalidation(tmp_path):
    root, mod = _mini_tree(tmp_path)
    cpath = str(tmp_path / "cache.json")
    r1 = analysis.run(root=root, cache_path=cpath)
    assert len(_prints(r1)) == 1 and r1.files_from_cache == 0
    r2 = analysis.run(root=root, cache_path=cpath)
    assert r2.files_from_cache == 1
    assert [f.to_dict() for f in _prints(r2)] == \
        [f.to_dict() for f in _prints(r1)]
    # Edit the file (force a different mtime too): cache must miss.
    with open(mod, "w") as f:
        f.write('def tick():\n    print("hi")\n    print("again")\n')
    os.utime(mod, (time.time() + 5, time.time() + 5))
    r3 = analysis.run(root=root, cache_path=cpath)
    assert r3.files_from_cache == 0
    assert len(_prints(r3)) == 2


def test_cache_touch_without_edit_rehashes_not_rescans(tmp_path):
    """mtime changed + content identical => the sha check reuses the
    cached result (a `touch` or fresh checkout must not go cold)."""
    root, mod = _mini_tree(tmp_path)
    cpath = str(tmp_path / "cache.json")
    analysis.run(root=root, cache_path=cpath)
    os.utime(mod, (time.time() + 60, time.time() + 60))
    r = analysis.run(root=root, cache_path=cpath)
    assert r.files_from_cache == 1


def test_cache_invalidated_by_checker_version(tmp_path, monkeypatch):
    root, _ = _mini_tree(tmp_path)
    cpath = str(tmp_path / "cache.json")
    analysis.run(root=root, cache_path=cpath)
    checker = analysis_core.get_checker("bare-print")
    monkeypatch.setattr(type(checker), "version",
                        checker.version + 1)
    r = analysis.run(root=root, cache_path=cpath)
    assert r.files_from_cache == 0          # digest changed: cold
    assert len(_prints(r)) == 1


def test_corrupt_cache_degrades_to_cold_run(tmp_path):
    root, _ = _mini_tree(tmp_path)
    cpath = str(tmp_path / "cache.json")
    with open(cpath, "w") as f:
        f.write("{not json")
    r = analysis.run(root=root, cache_path=cpath)
    assert len(_prints(r)) == 1


def test_baseline_update_round_trip(tmp_path):
    root, mod = _mini_tree(tmp_path)
    bpath = os.path.join(root, "lint_baseline.json")
    r1 = _run_mini(root, use_cache=False)
    assert r1.new and not r1.clean
    entries = baseline_lib.updated(r1.findings, {})
    # The TODO placeholder is rejected by the gate until justified.
    assert all(e["justification"].startswith("TODO")
               for e in entries.values())
    for e in entries.values():
        e["justification"] = "fixture: intentional"
    baseline_lib.save(bpath, entries)
    r2 = _run_mini(root, use_cache=False)
    assert r2.clean and not r2.new
    # Justifications survive a second update.
    entries2 = baseline_lib.updated(r2.findings,
                                    baseline_lib.load(bpath))
    assert all(e["justification"] == "fixture: intentional"
               for e in entries2.values())
    # Fixing the violation makes the entry stale -> gate fails again.
    with open(mod, "w") as f:
        f.write("def tick():\n    return 1\n")
    r3 = _run_mini(root, use_cache=False)
    assert r3.stale and not r3.clean


def test_baseline_count_budget(tmp_path):
    """N grandfathered hits; the N+1th still fails."""
    root, mod = _mini_tree(tmp_path)
    bpath = os.path.join(root, "lint_baseline.json")
    r1 = _run_mini(root, use_cache=False)
    entries = baseline_lib.updated(r1.findings, {})
    for e in entries.values():
        e["justification"] = "fixture: one print allowed"
    baseline_lib.save(bpath, entries)
    with open(mod, "a") as f:
        f.write('\ndef tock():\n    print("extra")\n')
    r2 = _run_mini(root, use_cache=False)
    assert len(r2.new) == 1 and not r2.clean


def test_partial_run_skips_stale_detection(tmp_path):
    root, _ = _mini_tree(tmp_path)
    bpath = os.path.join(root, "lint_baseline.json")
    baseline_lib.save(bpath, {
        "bare-print::skypilot_tpu/runtime/gone.py::print":
            {"count": 1, "justification": "file was deleted"}})
    full = _run_mini(root, use_cache=False)
    assert full.stale
    part = _run_mini(root, use_cache=False,
                     files=["skypilot_tpu/runtime/daemon.py"])
    assert part.partial and not part.stale
    assert len(part.findings) == 1          # still finds the print


def test_unjustified_baseline_fails_gate(tmp_path):
    root, _ = _mini_tree(tmp_path)
    bpath = os.path.join(root, "lint_baseline.json")
    r1 = _run_mini(root, use_cache=False)
    baseline_lib.save(bpath, baseline_lib.updated(r1.findings, {}))
    r2 = _run_mini(root, use_cache=False)
    assert r2.unjustified and not r2.clean
    # Justification checks are subset-independent: a partial
    # (--changed) run must fail on them too, not pass vacuously.
    r3 = _run_mini(root, use_cache=False,
                   files=["skypilot_tpu/runtime/daemon.py"])
    assert r3.partial and r3.unjustified and not r3.clean


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    root, mod = _mini_tree(tmp_path)
    with open(mod, "w") as f:
        f.write("def broken(:\n")
    r = _run_mini(root, use_cache=False)
    assert any(f.checker == "framework" and f.rule == "parse-error"
               for f in r.findings)


def test_finding_keys_are_line_stable(tmp_path):
    """Shifting code down must not change baseline identity."""
    root, mod = _mini_tree(tmp_path)
    k1 = _run_mini(root, use_cache=False).findings[0].key
    src = open(mod).read()
    with open(mod, "w") as f:
        f.write("# a new leading comment\n\n" + src)
    r = _run_mini(root, use_cache=False)
    assert r.findings[0].key == k1
    assert r.findings[0].line > 2


# ---------------------------------------------------------------------------
# CLI.

def test_cli_lint_json_clean():
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod
    res = CliRunner().invoke(cli_mod.cli, ["lint", "--json",
                                           "--no-cache"])
    assert res.exit_code == 0, res.output
    payload = json.loads(res.output)
    assert payload["clean"] is True
    assert payload["findings"] == []
    assert payload["baselined"] >= 20


def test_project_results_cached_and_invalidated_by_any_edit(tmp_path):
    """Project-scope findings are cached under a whole-tree content
    digest: a warm unchanged run reuses them; editing ANY file — or a
    checker's extra input like the docs catalog — recomputes."""
    root, mod = _mini_tree(tmp_path)
    docs = os.path.join(root, "docs")
    os.makedirs(docs)
    cat = os.path.join(docs, "observability.md")
    with open(cat, "w") as f:
        f.write("skytpu_fleet_scrape_up skytpu_fleet_merge_errors\n")
    cpath = str(tmp_path / "cache.json")

    def degenerate(res):
        return [f for f in res.findings
                if f.rule == "scan-degenerate"]

    r1 = analysis.run(root=root, cache_path=cpath)
    assert degenerate(r1)                   # mini tree: no metrics
    data1 = json.load(open(cpath))
    assert data1["files"]["//project"]["findings"]
    r2 = analysis.run(root=root, cache_path=cpath)
    assert degenerate(r2)                   # served from the cache
    # Editing any tree file invalidates the project digest.
    with open(mod, "a") as f:
        f.write("X = 1\n")
    r3 = analysis.run(root=root, cache_path=cpath)
    assert degenerate(r3)
    d3 = json.load(open(cpath))["files"]["//project"]["digest"]
    assert d3 != data1["files"]["//project"]["digest"]
    # Editing an extra input (the docs catalog) invalidates too.
    with open(cat, "a") as f:
        f.write("more\n")
    analysis.run(root=root, cache_path=cpath)
    d4 = json.load(open(cpath))["files"]["//project"]["digest"]
    assert d4 != d3


def test_checker_subset_run_never_touches_cache(tmp_path):
    """A --checker run's digest covers only the subset; writing it
    would clobber the full run's warm cache (and vice versa)."""
    root, _ = _mini_tree(tmp_path)
    cpath = str(tmp_path / "cache.json")
    r = _run_mini(root, cache_path=cpath)     # checkers subset
    assert len(r.findings) == 1
    assert not os.path.exists(cpath)
    full = analysis.run(root=root, cache_path=cpath)
    assert os.path.exists(cpath)
    before = open(cpath).read()
    _run_mini(root, cache_path=cpath)
    assert open(cpath).read() == before       # untouched
    again = analysis.run(root=root, cache_path=cpath)
    assert again.files_from_cache == full.files_scanned


def test_cli_baseline_update_refused_on_subset_runs():
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod
    for args in (["lint", "--baseline-update", "--changed"],
                 ["lint", "--baseline-update", "--checker",
                  "bare-print"],
                 ["lint", "--baseline-update",
                  "skypilot_tpu/utils/db.py"]):
        res = CliRunner().invoke(cli_mod.cli, args)
        assert res.exit_code != 0, args
        assert "full run" in res.output


def test_cli_lint_nonexistent_path_errors():
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod
    res = CliRunner().invoke(
        cli_mod.cli, ["lint", "/tmp/does-not-exist-xyz.py",
                      "--no-cache"])
    assert res.exit_code != 0
    assert "resolve" in res.output


def test_cli_lint_checker_filter_unknown():
    from click.testing import CliRunner

    from skypilot_tpu.client import cli as cli_mod
    res = CliRunner().invoke(
        cli_mod.cli, ["lint", "--checker", "no-such-checker"])
    assert res.exit_code != 0
    assert "no-such-checker" in res.output


def test_all_five_checker_families_registered():
    names = {c.name for c in analysis_core.all_checkers()}
    assert {"retrace-safety", "host-sync", "lock-discipline",
            "typed-errors", "bare-print", "adhoc-retry",
            "metric-catalog"} <= names
