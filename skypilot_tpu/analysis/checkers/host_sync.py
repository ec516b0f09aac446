"""host-sync: hidden device syncs in the serving/training hot loops.

The engine's throughput model assumes the step/burst/chunk loops only
*dispatch* device programs; every host fetch (``int(tok)``,
``np.asarray``, ``.item()``, ``.block_until_ready()``) is a full
dispatch-pipeline drain — the exact stall the async burst double-
buffering exists to avoid. A sync that belongs there (the completion
fetch IS the sync point) is baselined with a justification; a new one
fails the gate so it gets argued about in review instead of shipped.

Scope is the hot loops only — bench files and tests measure by
syncing, that is their job.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set

from skypilot_tpu.analysis.checkers import _util
from skypilot_tpu.analysis.core import Checker, FileContext, register
from skypilot_tpu.analysis.findings import Finding

# rel path -> function/method *leaf* names forming the hot loop.
# (Admission, chunking, decode and completion paths in the engine; the
# serving loop in the server; the per-step wrapper in the trainer.)
_SCOPES: Dict[str, Set[str]] = {
    "skypilot_tpu/infer/engine.py": {
        "step", "step_burst", "step_decode_once", "decode_burst",
        "dispatch_decode_burst", "complete_decode_burst",
        "prefill_chunk_step", "run_to_completion", "_admit", "admit",
        "_dispatch_wave", "_complete_wave", "_claim_chunked",
        "_store_prefix",
        # Crash recovery (PR 19): the dispatch seams grew thin
        # failure-boundary wrappers; the hot-loop bodies moved to
        # *_impl and stay in scope under their new names.
        "_admit_impl", "_prefill_chunk_impl", "_spec_decode_burst_impl",
        "_dispatch_decode_burst_impl", "_complete_decode_burst_impl",
        "recover",
        # Queued chunks (PR 47): a non-final chunk is dispatched and
        # landed later; the landing's wait is the one sync a chunk
        # that nobody fetches still has, and it is free wherever a
        # later program has landed already.
        "_land_chunks", "_chunk_landed",
        # Paged-KV block management (PR 7): all host-side numpy/list
        # bookkeeping — a device fetch here would drain the dispatch
        # pipeline once per claim/retire.
        "table_device", "_alloc_blocks", "_wave_claim",
        "_free_slot_blocks", "_need_blocks",
        # Speculative decode (PR 8): drafting is pure host work (the
        # n-gram index) and the verify burst's ONE deliberate fetch is
        # its completion sync — anything else here stalls the verify/
        # accept hot path once per burst.
        "spec_decode_burst", "_draft_for",
        # Span-bucketed attention + lazy growth (PR 9): bucket
        # selection and block headroom run per burst from HOST state
        # (request token lists, the numpy block table) — a device
        # fetch to pick a span would stall every dispatch.
        "_round_slots", "_span_for", "_span_arg", "_slot_rows",
        "_ensure_headroom",
        # Flight recorder (PR 10): the per-burst record is assembled
        # from host bookkeeping inside the step/burst/chunk loops — a
        # device fetch here would stall the very dispatch pipeline
        # the recorder observes.
        "_record_flight",
        # Multi-tenant QoS (PR 11): scheduling, re-queue and
        # preemption-by-eviction run before every admission pass from
        # HOST state (request token lists, the numpy block table,
        # refcounts) — eviction is a table edit, and a device fetch to
        # pick a victim would stall admission itself.
        "_requeue", "_ctx", "_resumable", "preempt_slot",
        "_preempt_for_waiting",
        # Paged-attention kernel + KV quotas (PR 12): the kernel
        # DISPATCH seam stays the same burst methods above (the flag
        # is engine-constant), but the per-tenant block accounting
        # runs at every claim/growth/free and the quota check per
        # admission pass — all host numpy-table bookkeeping; a device
        # fetch to count a tenant's blocks would stall admission.
        "_kv_quota", "_kv_quota_blocked", "_set_tenant_kv",
        "_sync_kv_charge",
        # Multi-LoRA adapter catalog (PR 13): acquire/release and the
        # per-slot adapter-id bookkeeping run at every claim/retire,
        # and the aid device-copy cache mirrors table_device — all
        # host dict/array work; a device fetch to pick a pool slot
        # would stall admission exactly like a block-count fetch.
        "_acquire_adapter", "_release_adapter", "_set_slot_adapter",
        "aid_device", "_lora_args", "_fail_request",
        # Draft-model pipeline (PR 14): drafter-mode resolution runs
        # per slot per verify round from pure request bookkeeping — a
        # device fetch to pick a drafter rung would stall every spec
        # dispatch.
        "_spec_mode",
        # Request forensics (PR 17): stall-episode bookkeeping rides
        # every claim attempt, and the retire record + P^2 tail
        # observe ride every retirement — all pure host dict/float
        # work; a device fetch inside _retire would stall the very
        # completion path whose latency the ledger decomposes.
        "_retire", "_mark_stall", "_end_stall", "_observe_tail",
        # Phase annotations (PR 25): the bodies the new
        # ``timeline.phase`` blocks wrap moved to helpers of their own
        # and stay in scope under their new names. ``timeline.phase``
        # itself performs no device sync: its arguments are host ints
        # and floats, and the profiler annotation it enters is inert
        # unless a trace is running.
        "_admit_pass", "_launch_wave", "_commit_burst", "_retire_impl",
    },
    # Model-backed drafter (PR 14): draft_batch/rollout run once per
    # verify round on the engine loop; everything except the draft
    # path's OWN completion fetch (the next verify window needs the
    # token values — baselined with justification) must stay pure
    # host bookkeeping, or the pipeline stalls the very verify
    # in-flight window it exists to overlap.
    "skypilot_tpu/infer/draft.py": {
        "draft_batch", "rollout", "_apply_pending", "_apply_rollout",
        "_sync_slot", "_ingest", "_dispatch_sync",
        "_dispatch_rollout", "release", "_acquire", "table_device",
        "_span_for", "_span_arg", "claimed", "stats",
    },
    # Adapter-catalog residency bookkeeping: acquire runs at every
    # claim (the hot-load inside it is a cold path by design — a
    # demand load IS a device dispatch — but the bookkeeping around
    # it must stay pure host work), release at every retire.
    "skypilot_tpu/infer/adapters.py": {
        "acquire", "release", "_grab_slot", "check", "names",
        "resident_count", "slot_names", "pins",
    },
    # QoS scheduler + admission control: the DRR reorder runs on the
    # engine loop before every admission pass and the admission check
    # runs per HTTP request — both are pure host bookkeeping over
    # request lists and token buckets.
    "skypilot_tpu/infer/qos.py": {
        "reorder", "request_cost", "weight", "admit", "take",
        "tenant_label",
    },
    # Flight recorder + compile watch internals: record() runs once
    # per burst on the engine loop and the watch wrapper rides EVERY
    # jit dispatch — both must stay pure host work.
    "skypilot_tpu/observability/flight.py": {
        "record", "wrap", "tail", "since", "drain_new", "summary",
    },
    # Device-truth attribution (PR 16): the calibrator's tick/estimate
    # and the roofline cost model ride every dispatch and every
    # _record_flight call — pure host arithmetic, except timed_call's
    # ONE deliberate block_until_ready: that bracket IS the
    # calibration measurement, fires on a sampled ~1/64 of hit-path
    # dispatches, and is baselined with justification.
    "skypilot_tpu/observability/attribution.py": {
        "timed_call", "tick", "update", "estimate", "record_cost",
        "set_bytes", "snapshot", "total",
    },
    # Request forensics (PR 17): the ledger builder replays flight
    # records on demand (CLI/debug endpoint — cold), but the P^2
    # quantile observe and the exemplar pin run inline at every
    # retirement on the engine loop — pure host arithmetic over
    # floats and dicts.
    "skypilot_tpu/observability/forensics.py": {
        "observe", "pin", "value", "_parabolic",
    },
    "skypilot_tpu/infer/server.py": {
        "_loop", "_step", "_drain_inbox", "_flush_streams",
        "_complete_burst", "_on_wave",
        # PR 25: the inbox and results bodies moved under their phase
        # annotations.
        "_enqueue", "_deliver_finished", "_has_work",
    },
    "skypilot_tpu/train/trainer.py": {
        "_instrument_step", "observe_loss",
        # Training goodput (PR 18): the compile-watch key function
        # rides EVERY train-step dispatch — shape metadata reads only,
        # never array values.
        "_batch_key_fn",
    },
    # Training goodput forensics (PR 18): the step ledger's
    # start/phase/end path and the cursor/bucket credits run once per
    # train step on the loop thread, and the anomaly watchdog's
    # observe folds in the losses the logging cadence ALREADY fetched
    # — all pure host float/dict arithmetic; a device fetch here would
    # stall the very step pipeline whose goodput it measures.
    "skypilot_tpu/observability/goodput.py": {
        "step_start", "phase", "step_end", "account", "snapshot",
        "_credit_locked", "_advance_locked", "observe",
    },
}

_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
               "numpy.array", "jax.device_get"}


@register
class HostSyncChecker(Checker):
    name = "host-sync"
    description = ("host syncs (.block_until_ready, np.asarray, "
                   ".item, int()/float() fetches) inside the engine "
                   "step/burst/chunk loops and the trainer step path")
    scope = "file"
    # v2: paged-KV block-management methods joined the engine scope.
    # v3: the speculative verify/accept path joined it.
    # v4: span-selection + lazy-growth methods joined it.
    # v5: the flight-recorder record path + compile-watch wrapper.
    # v6: QoS — the DRR scheduler/admission (infer/qos.py) and the
    #     preemption-by-eviction path joined the scope.
    # v7: paged-attention kernel rollout (PR 12) — the per-tenant
    #     KV-block quota/charge bookkeeping joined the engine scope;
    #     the bump rescans the edited dispatch seam cold.
    # v8: multi-LoRA adapter catalog (PR 13) — the engine's adapter
    #     acquire/release/aid bookkeeping and the catalog's residency
    #     path (infer/adapters.py) joined the scope; the bump rescans
    #     the edited claim/retire hot path cold.
    # v9: draft-model speculation + async pipeline (PR 14) — the
    #     engine's drafter-mode ladder and the DraftEngine's
    #     draft/rollout/lockstep path (infer/draft.py) joined the
    #     scope; the bump rescans the edited spec hot path cold.
    # v10: device-truth attribution (PR 16) — the calibrator tick/
    #     estimate path, the roofline cost model and the HBM ledger
    #     (observability/attribution.py) joined the scope; the one
    #     deliberate calibration bracket is baselined.
    # v11: request forensics (PR 17) — the engine's retire/stall/tail
    #     path and the P^2 observe + exemplar pin
    #     (observability/forensics.py) joined the scope; the bump
    #     rescans the edited retirement hot path cold.
    # v12: training goodput (PR 18) — the goodput step-ledger/anomaly
    #     path (observability/goodput.py) and the trainer's compile-
    #     watch key function joined the scope; the calibrator's
    #     sampled block_until_ready bracket stays baselined from v10.
    # v13: crash recovery (PR 19) — the dispatch-seam bodies moved to
    #     *_impl names and recover() joined the scope; the bump
    #     rescans the renamed hot paths cold.
    # v14: phase annotations (PR 25) — the bodies wrapped by
    #     ``timeline.phase`` blocks moved to helpers that joined the
    #     scope (the burst's int() loop is now ``_commit_burst``'s).
    # v15: one decode program a round (PR 26) — ``_span_groups`` became
    #     ``_round_slots``; the bump rescans the renamed helper cold.
    version = 15

    def check_file(self, ctx: FileContext) -> List[Finding]:
        scoped = _SCOPES.get(ctx.rel)
        if not scoped:
            return []
        out: List[Finding] = []
        for qual, _cls, func in ctx.functions:
            leaf = qual.split(".")[-1]
            if leaf not in scoped:
                continue
            out.extend(self._check_func(ctx, qual, func))
        return out

    def _check_func(self, ctx: FileContext, qual: str,
                    func: ast.AST) -> List[Finding]:
        out: List[Finding] = []

        def finding(node, pattern, message):
            out.append(Finding(
                checker=self.name, rule="host-sync", path=ctx.rel,
                line=node.lineno, col=node.col_offset,
                message=f"in hot loop `{qual}`: {message}",
                ident=f"{qual}:{pattern}",
                hint="a host fetch drains the dispatch pipeline; "
                     "move it to the completion path or keep the "
                     "value on device (baseline deliberate sync "
                     "points with a justification)"))

        for node in _util.body_walk(func):
            # A nested def is its own scope when listed; skip bodies of
            # nested helpers NOT in the scope set? They run inline —
            # keep them: the loop calls them synchronously.
            if not isinstance(node, ast.Call):
                continue
            name = _util.call_name(node) or ""
            leaf = name.split(".")[-1]
            attr = (node.func.attr
                    if isinstance(node.func, ast.Attribute) else None)
            if attr == "block_until_ready":
                finding(node, "block_until_ready",
                        "`.block_until_ready()` stalls the loop "
                        "thread on the device")
            elif attr == "item" and not node.args:
                finding(node, "item", "`.item()` is a device fetch")
            elif name in _SYNC_CALLS and node.args \
                    and not _util.is_constant_expr(node.args[0]):
                finding(node, leaf,
                        f"`{name}(...)` fetches the array to the host")
            elif name in {"int", "float"} and len(node.args) == 1 \
                    and not _util.is_constant_expr(node.args[0]) \
                    and not self._host_cast(node.args[0]):
                finding(node, leaf,
                        f"`{leaf}(...)` on a device value is a "
                        f"blocking fetch")
        return out

    def _host_cast(self, arg: ast.AST) -> bool:
        """Casts that can't touch the device: len(), time values,
        environment reads, pure-host attributes."""
        for node in ast.walk(arg):
            if isinstance(node, ast.Call):
                name = _util.call_name(node) or ""
                if name == "len" or name.startswith(("os.", "time.")):
                    return True
        return False
