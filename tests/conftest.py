"""Test environment: simulate an 8-device TPU mesh on CPU.

Mirrors the reference's test strategy (SURVEY.md §4): multi-"executor"
protocol tests in one process. Here the fake cluster is XLA's virtual CPU
device feature — 8 devices in one process — so every sharding/collective
path runs exactly as it would on an 8-chip slice.

Must run before anything imports jax.
"""
import os

# Force CPU even on a host with real TPU hardware: the suite needs an
# 8-device mesh whatever the box has. The env vars also reach the fresh
# interpreters the tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Tiered suite: compile-heavy tests are marked `slow` and SKIPPED by default
# so the default run stays under ~6 minutes on a CPU host (a driver-side
# wall-clock cap must never masquerade as a code failure). Run everything
# with `pytest --runslow` or HARMONY_RUN_SLOW=1. The slow set is maintained
# from measured durations (tests >=4s each; together they are ~60% of the
# full suite's wall time) — EXCEPT deliberate default-tier sentinels:
# test_multihost.py::test_pod_smoke_default_tier (~20s) stays in the
# default tier ON PURPOSE so a pod-path regression cannot ship green under
# the default run; do not move it here during duration-based maintenance.
# ---------------------------------------------------------------------------

_SLOW_TESTS = {
    "test_multihost.py::test_two_process_distributed_job",
    "test_multihost.py::test_pod_concurrent_carved_tenants",
    "test_multihost.py::test_pod_share_all_overlapping_tenants[2-4]",
    "test_multihost.py::test_pod_share_all_overlapping_tenants[3-2]",
    "test_multihost.py::test_pod_share_all_overlapping_tenants[6-1]",
    # the v5p-32 control-plane shape: 8 followers x 1 device (round-5
    # verdict — validate share-all/admission/heartbeats/arbiter at the
    # real deployment width; loss parity + protocol invariants, not wall)
    "test_multihost.py::test_pod_share_all_overlapping_tenants[9-1]",
    "test_multihost.py::test_pod_share_all_pregel_and_dolphin_overlap",
    "test_multihost.py::test_pod_share_all_tenant_storm[2-2]",
    "test_multihost.py::test_pod_share_all_tenant_storm[4-1]",
    "test_multihost.py::test_pod_many_tenant_mixed_admission",
    "test_multihost.py::test_pod_units_tolerate_dcn_latency",
    "test_multihost.py::test_pod_reshard_multiworker_ssp",
    "test_multihost.py::test_pod_remote_only_plan_epoch_floor",
    "test_multihost.py::test_pod_admission_fifo_no_starvation[2-2]",
    "test_multihost.py::test_pod_admission_fifo_no_starvation[6-1]",
    "test_multihost.py::test_pod_long_job_survives_heartbeat_window[2-2-3]",
    "test_multihost.py::test_pod_long_job_survives_heartbeat_window[6-1-6]",
    "test_multihost.py::test_pod_killed_follower_poisons_fast",
    "test_multihost.py::test_pod_live_grow_mid_training",
    "test_multihost.py::test_pod_auto_resume_after_follower_death",
    "test_multihost.py::test_pod_auto_resume_multiworker_completes",
    "test_multihost.py::test_pod_checkpoint_restore_cross_topology",
    "test_multihost.py::test_pod_training_chkp_chain_restores_in_parent[posix]",
    "test_multihost.py::test_pod_training_chkp_chain_restores_in_parent[orbax]",
    "test_multihost.py::test_pod_multiworker_chkp_chain_matches_lockstep",
    "test_multihost.py::test_pod_live_reshard_across_process_subsets[tcp]",
    "test_multihost.py::test_pod_live_reshard_across_process_subsets[file]",
    "test_multihost.py::test_pod_block_migration_moves_only_moved_bytes[tcp]",
    "test_multihost.py::test_pod_block_migration_moves_only_moved_bytes[file]",
    "test_multihost.py::test_pod_block_migration_follower_to_follower",
    "test_multihost.py::test_pod_plan_driven_migration_mid_training",
    "test_multihost.py::test_pod_optimizer_loop_elasticity",
    "test_multihost.py::test_pod_collective_deferred_eval[1]",
    "test_multihost.py::test_pod_collective_deferred_eval[2]",
    "test_multihost.py::test_pod_ssp_multiworker_gates_and_matches_lockstep_baseline",
    "test_multihost.py::test_pod_jobserver_end_to_end[2-4]",
    "test_multihost.py::test_pod_jobserver_end_to_end[3-2]",
    "test_moe.py::test_expert_parallel_gradients",
    "test_moe.py::test_expert_parallel_matches_reference",
    "test_moe.py::test_moe_matches_per_token_reference",
    "test_moe.py::TestMoELM::test_moe_lm_learns_with_aux",
    "test_moe.py::TestMoELM::test_single_expert_equals_dense",
    "test_moe.py::TestMoELM::test_moe_cache_decode_matches_forward",
    "test_moe.py::TestMoELM::test_sp_step_carries_aux",
    "test_moe.py::TestMoELM::test_ep_step_matches_single_device_ce",
    "test_moe.py::TestMoELM::test_ep_step_learns",
    "test_moe.py::test_capacity_drops_tokens",
    "test_apps.py::TestSparseLDAOverflowConsistency::test_out_of_domain_ids_are_ignored_not_corrupting",
    "test_widedeep.py::TestSparseDurability::test_sparse_deferred_eval_at_shutdown",
    "test_widedeep.py::TestSparseDurability::test_factory_update_fn_restores_in_fresh_registry",
    "test_widedeep.py::TestFM::test_duplicate_ids_fold_in_push",
    "test_widedeep.py::TestSparseMode::test_sparse_widedeep_learns",
    "test_widedeep.py::TestSparseMode::test_sparse_fm_learns_on_full_domain_ids",
    "test_ops.py::test_ring_attention_gradients",
    "test_ops.py::TestA2AAttention::test_matches_full_attention[False]",
    "test_ops.py::TestA2AAttention::test_matches_full_attention[True]",
    "test_ops.py::test_ring_attention_matches_naive[False]",
    "test_ops.py::test_ring_attention_matches_naive[True]",
    "test_ops.py::test_flash_gradients_match_naive",
    "test_models.py::test_sp_step_matches_single_device",
    "test_models.py::test_sp_training_loop_learns",
    "test_models.py::test_remat_same_loss_and_grads",
    "test_models.py::test_trainer_spi_through_worker_loop",
    "test_models.py::test_parallel_step_a2a_tier",
    "test_models.py::test_sp_step_a2a_matches_ring",
    "test_models.py::test_parallel_step_matches_single_device",
    "test_models.py::TestStatefulOptimizers::test_momentum_learns",
    "test_models.py::TestStatefulOptimizers::test_adam_learns_and_tracks_steps",
    "test_models.py::TestStatefulOptimizers::test_optimizer_state_survives_checkpoint_restore",
    "test_models.py::test_forward_shapes_and_finite",
    "test_models.py::test_load_text_tokens_and_trains",
    "test_cli.py::test_cli_run_standalone[lm]",
    "test_pipeline.py::test_pipeline_transformer_blocks",
    "test_pipeline.py::test_pipeline_gradients_match",
    "test_pipeline.py::test_pp_train_step_matches_single_device",
    "test_pipeline.py::test_pp_train_step_learns",
    "test_hashtable.py::TestUpdateModes::test_min_mode",
    "test_hashtable.py::TestUpdateModes::test_assign_mode_last_wins",
    "test_hashtable.py::TestUpdateModes::test_post_invariant_only_on_touched",
    "test_hashtable.py::TestUpdateModes::test_assign_exact_across_magnitudes",
    "test_hashtable.py::TestCollisionsAndOverflow::test_collision_heavy_single_block",
    "test_hashtable.py::TestCollisionsAndOverflow::test_batch_race_for_one_empty_slot",
    "test_hashtable.py::TestShardedAndElastic::test_reshard_preserves_contents",
    "test_hashtable.py::TestRuntimeIntegration::test_master_creates_hash_table",
    "test_apps.py::TestSparseLDA::test_sparse_topics_concentrate",
    "test_apps.py::TestSparseLDA::test_sparse_matches_dense_semantics",
    "test_gbt.py::TestHistModes::test_matmul_hist_matches_scatter",
    "test_gbt.py::TestGBTRegression::test_loss_decreases_and_fits",
    "test_gbt.py::TestGBTClassification::test_multiclass_softmax",
    "test_gbt.py::TestGBTClassification::test_binary_logistic",
    "test_regressions.py::test_shutdown_timeout_bounds_wedged_job",
    "test_optim.py::test_adagrad_in_lm_trainer",
    "test_migration.py::TestSparseTableMigration::test_concurrent_migration_during_sparse_training",
    "test_vit.py::test_sharded_step_matches_single_device",
    "test_vit.py::test_learns_and_classifies",
    "test_generate.py::test_greedy_matches_stepwise_argmax",
    "test_vit.py::test_vit_trainer_through_worker_loop",
}


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (the full-coverage tier)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: compile-heavy test, skipped unless --runslow"
    )
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection test (harmony_tpu.faults); "
        "the fast smoke set runs in tier-1, process-killing pod tests are "
        "also marked slow",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded chaos-orchestrator test (harmony_tpu.faults.chaos); "
        "schedule determinism + fast scenarios run in tier-1, the HA "
        "takeover scenarios are also marked slow (bin/chaos.sh runs both "
        "tiers)",
    )


def pytest_collection_modifyitems(config, items):
    run_slow = (config.getoption("--runslow")
                or os.environ.get("HARMONY_RUN_SLOW") == "1")
    skip = pytest.mark.skip(reason="slow tier: use --runslow / HARMONY_RUN_SLOW=1")
    for item in items:
        rel = item.nodeid.split("/")[-1]
        if rel in _SLOW_TESTS or item.get_closest_marker("slow"):
            item.add_marker(pytest.mark.slow)
            if not run_slow:
                item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def mesh8(devices):
    from harmony_tpu.parallel import build_mesh

    return build_mesh(devices, data=2, model=4)


@pytest.fixture()
def mesh_dp(devices):
    from harmony_tpu.parallel import build_mesh

    return build_mesh(devices, data=8, model=1)
