"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's offline-test strategy (reference:
tests/common_test_fixtures.py — everything cloud is mocked, the logic runs
for real). Here additionally the *device* layer is virtualized: 8 CPU
devices stand in for a TPU slice so sharding/gang logic is exercised
without hardware.

Must run before any JAX backend initialization (XLA flags parse once,
and a backend cannot be re-platformed after its first client).
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache, shared across test processes, the
# subprocess servers/controllers the e2e tests spawn (they inherit the
# env), and successive runs: the suite's wall time is dominated by
# recompiling identical tiny CPU programs. The same helper every entry
# point calls decides where it lives.
from skypilot_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The paged-attention and grouped-SwiGLU kernels compile with Mosaic by
# default; the CPU suite is the one place that asks for the Pallas
# interpreter.
from skypilot_tpu.ops import grouped_ffn, paged_attention  # noqa: E402

paged_attention.INTERPRET = True
grouped_ffn.INTERPRET = True

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--run-slow", action="store_true", default=False,
        help="include tests marked slow (the full profile; also enabled "
             "by SKYTPU_TESTS_FULL=1)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy / e2e test, excluded from the default fast "
        "profile (run with --run-slow or SKYTPU_TESTS_FULL=1)")


# The fast-profile contract, maintained centrally from measured
# durations (pytest --durations): every test here took >= ~6.5s on the
# suite box. A stale entry (renamed test) just runs in both profiles.
_SLOW_TESTS = {
    "tests/test_advice_r3.py::test_moe_zigzag_matches_contiguous",
    "tests/test_advice_r3.py::test_moe_zigzag_nondivisible_falls_back",
    "tests/test_api_server.py::test_launch_via_server",
    "tests/test_api_server.py::test_request_log_streaming",
    "tests/test_checkpoints.py::test_resume_continues_identically",
    "tests/test_checkpoints.py::test_roundtrip_sharded",
    "tests/test_e2e_local.py::test_failover_retry_until_up",
    "tests/test_e2e_local.py::test_gang_fail_one_kills_all",
    "tests/test_e2e_local.py::test_stop_start_down",
    "tests/test_flash_attention.py::test_backward_matches_oracle",
    "tests/test_flash_attention.py::test_segment_backward_matches_oracle",
    "tests/test_infer.py::test_continuous_batching_isolation",
    "tests/test_infer.py::test_engine_with_tp_sharded_params",
    "tests/test_infer.py::test_incremental_decode_matches_full_forward",
    "tests/test_infer.py::test_mixed_bucket_admission",
    "tests/test_infer.py::test_max_wave_splits_admission",
    "tests/test_infer.py::test_moe_engine_serves",
    "tests/test_infer.py::test_sampling_temperature_valid",
    "tests/test_infer.py::test_weights_int8_composes_with_kv_int8",
    "tests/test_infer.py::test_weights_int8_engine_generates_sensibly",
    "tests/test_kubernetes_provision.py::test_query_and_wait",
    "tests/test_kubernetes_provision.py::test_run_instances_applies_all_pods",
    "tests/test_llama.py::test_chunked_xent_matches_full",
    "tests/test_llama.py::test_overfit_tiny_batch",
    "tests/test_lora.py::test_adapters_learn_base_frozen",
    "tests/test_lora.py::test_sharded_lora_step",
    "tests/test_managed_jobs.py::test_controller_log_streams_to_client",
    "tests/test_managed_jobs.py::test_jobs_survive_client_death",
    "tests/test_managed_jobs.py::test_launching_parallelism_gate",
    "tests/test_managed_jobs.py::test_managed_job_cancel",
    "tests/test_managed_jobs.py::test_managed_job_recovers_from_preemption",
    "tests/test_managed_jobs.py::test_managed_job_succeeds",
    "tests/test_managed_jobs.py::test_managed_job_user_failure_no_recovery",
    "tests/test_managed_jobs.py::test_queue_lists_jobs",
    "tests/test_managed_jobs.py::test_unknown_strategy_rejected",
    "tests/test_managed_jobs.py::test_pipeline_runs_tasks_sequentially",
    "tests/test_managed_jobs.py::test_pipeline_failure_stops_chain",
    "tests/test_managed_jobs.py::test_pipeline_cancel_mid_run_stops_chain",
    "tests/test_infer_tp.py::test_server_main_tp_end_to_end",
    "tests/test_infer_tp.py::test_tp_engine_matches_single_device",
    "tests/test_infer_tp.py::test_sharded_init_materializes_on_mesh",
    "tests/test_infer_tp.py::test_tp_engine_matches_w8a8_and_kv_int8",
    "tests/test_moe.py::test_loss_decreases",
    "tests/test_moe.py::test_train_step_on_ep_mesh",
    "tests/test_observability.py::test_benchmark_launch_local",
    "tests/test_pipeline.py::test_pipelined_matches_sequential",
    "tests/test_pipeline.py::test_train_step_on_pp_mesh",
    "tests/test_recipes.py::test_evaluate_cli_smoke",
    "tests/test_recipes.py::test_train_run_cli_smoke",
    "tests/test_recipes.py::test_train_run_qlora_cli_smoke",
    "tests/test_ring_attention.py::test_packed_model_with_sp",
    "tests/test_ring_attention.py::test_ring_gqa_gradients",
    "tests/test_ring_attention.py::test_ring_gradients_match",
    "tests/test_ring_attention.py::test_ring_segments_gradients",
    "tests/test_ring_attention.py::test_train_step_with_sp",
    "tests/test_ring_attention.py::test_zigzag_gradients_match",
    "tests/test_runtime_fixes.py::test_cost_report_whole_cluster_price",
    "tests/test_serve.py::test_autoscaler_scales_up_under_load",
    "tests/test_serve.py::test_lb_503_when_no_replicas",
    "tests/test_serve.py::test_replica_failure_recovery",
    "tests/test_serve.py::test_rolling_update_zero_downtime",
    "tests/test_serve.py::test_serve_survives_client_death",
    "tests/test_serve.py::test_serve_up_ready_balance_down",
    "tests/test_serve.py::test_streaming_through_lb",
    "tests/test_serve.py::test_tls_termination",
    "tests/test_spot_mix.py::test_spot_preemption_backfills_ondemand",
    "tests/test_qlora.py::test_zero_adapters_match_fp_model",
    "tests/test_qlora.py::test_qlora_adapters_learn",
    "tests/test_qlora.py::test_qlora_grads_only_adapters",
    "tests/test_qlora.py::test_random_quantized_params_device_side",
    "tests/test_sharding.py::test_multislice_mesh_virtual_slices",
    "tests/test_sharding.py::test_sharded_matches_unsharded",
    "tests/test_sharding.py::test_sharded_train_step_runs",
    "tests/test_vit.py::test_memorizes_fixed_batch",
    "tests/test_vit.py::test_sharded_train_step",
    # Second tier (warm-cache durations >= ~4s on the 1-core suite box).
    "tests/test_checkpoints.py::test_max_to_keep",
    "tests/test_multislice_env.py::test_jax_distributed_initializes_from_injected_env",
    "tests/test_lora.py::test_identity_at_init",
    "tests/test_ring_attention.py::test_model_zigzag_matches_contiguous",
    "tests/test_ring_attention.py::test_model_zigzag_nondivisible_falls_back",
    "tests/test_ring_attention.py::test_ring_matches_xla_forward",
    "tests/test_ring_attention.py::test_ring_sp4",
    "tests/test_ring_attention.py::test_ring_nondivisible_dims_replicate",
    "tests/test_ring_attention.py::test_ring_gqa_tp_divides_q_not_kv",
    "tests/test_ring_attention.py::test_model_forward_with_sp",
    "tests/test_pipeline.py::test_pp_sharded_loss_matches_unsharded",
    "tests/test_pipeline.py::test_param_axes_match_shapes",
    "tests/test_pipeline.py::test_1f1b_grads_match_gpipe",
    "tests/test_pipeline.py::test_1f1b_memory_flat_in_microbatches",
    "tests/test_pipeline.py::test_1f1b_on_pp_mesh",
    "tests/test_vit.py::test_forward_shapes",
    "tests/test_infer.py::test_kv_int8_engine_matches_fp_closely",
    "tests/test_infer.py::test_eos_stops_decode",
    "tests/test_infer.py::test_oversized_prompt_rejected_at_submit",
    "tests/test_e2e_local.py::test_multihost_rank_assignment",
    "tests/test_remote_cluster.py::test_multihost_gang_over_fake_ssh",
    "tests/test_remote_cluster.py::test_gang_fail_one_kills_all_over_fake_ssh",
    "tests/test_remote_cluster.py::test_job_survives_client_death",
    "tests/test_remote_cluster.py::test_remote_hosts_import_rsynced_framework",
    "tests/test_moe.py::test_ep_sharded_matches_unsharded",
    "tests/test_recipes.py::test_collectives_bench_smoke",
    "tests/test_runtime_fixes.py::test_jobs_run_fifo_one_at_a_time",
    "tests/test_llama.py::test_causality",
    # Third tier (>= ~3s): the 2-minute fast profile on a 1-core box
    # leaves ~1 smoke test per subsystem fast; everything compile- or
    # subprocess-heavy runs in the full profile.
    "tests/test_checkpoints.py::test_restore_missing_raises",
    "tests/test_vit.py::test_param_count_matches",
    "tests/test_ring_attention.py::test_ring_gqa_unrepeated_kv",
    "tests/test_ring_attention.py::test_ring_segments_gqa_sp4",
    "tests/test_ring_attention.py::test_model_odd_seq_falls_back_to_local",
    "tests/test_remote_cluster.py::test_fresh_client_sees_queue_and_can_exec",
    "tests/test_remote_cluster.py::test_autodown_fires_from_cluster_side",
    "tests/test_remote_cluster.py::test_autostop_fires_from_cluster_side",
    "tests/test_remote_cluster.py::test_tail_logs_bounded_despite_lingering_child",
    "tests/test_e2e_local.py::test_exec_on_existing_cluster_and_queue",
    "tests/test_e2e_local.py::test_launch_end_to_end",
    "tests/test_e2e_local.py::test_env_contract_injected",
    "tests/test_e2e_local.py::test_refresh_detects_external_teardown",
    "tests/test_e2e_local.py::test_setup_and_envs",
    "tests/test_runtime_fixes.py::test_autodown_daemon_removes_cluster",
    "tests/test_runtime_fixes.py::test_tail_logs_unknown_job_raises",
    "tests/test_runtime_fixes.py::test_autostop_daemon_stops_idle_cluster",
    "tests/test_cli.py::test_launch_local_roundtrip",
    "tests/test_cli.py::test_launch_from_yaml",
    "tests/test_infer.py::test_slots_recycled",
    "tests/test_flight.py::test_flight_smoke_bench_wiring",
    "tests/test_flight.py::test_warm_programs_then_zero_unexpected",
    "tests/test_flight.py::test_chunk_verify_interleave_consistency",
    "tests/test_infer_server.py::test_generate_greedy_matches_engine",
    "tests/test_api_server.py::test_failed_request_propagates_error",
    "tests/test_api_server.py::test_api_status_lists_requests",
    "tests/test_moe.py::test_full_capacity_routes_all_tokens",
    "tests/test_cli.py::test_check",
}


def pytest_collection_modifyitems(config, items):
    run_slow = (config.getoption("--run-slow")
                or bool(os.environ.get("SKYTPU_TESTS_FULL")))
    skip = pytest.mark.skip(
        reason="slow (fast profile); use --run-slow or SKYTPU_TESTS_FULL=1")
    for item in items:
        base = item.nodeid.split("[")[0]
        if base in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        if not run_slow and "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def mesh8():
    from skypilot_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh(mesh_lib.MeshShape(dp=2, fsdp=2, tp=2))


@pytest.fixture()
def tiny_cfg():
    from skypilot_tpu.models import llama
    return llama.CONFIGS["llama3-tiny"]


def ttft_fams(fast, slow):
    """Cumulative TTFT histogram family: ``fast`` samples <= 0.1 s,
    ``slow`` in (0.1, 5] — the synthetic feed the burn-rate
    autoscaler/SLO tests observe (shared by test_qos/test_chaos)."""
    cum, samples = 0, []
    for le, n in (("0.1", fast), ("5", slow), ("+Inf", 0)):
        cum += n
        samples.append(({"__name__": "skytpu_ttft_seconds_bucket",
                         "le": le}, float(cum)))
    samples.append(({"__name__": "skytpu_ttft_seconds_count"},
                    float(cum)))
    return {"skytpu_ttft_seconds": {"type": "histogram",
                                    "samples": samples}}
