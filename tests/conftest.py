"""Pytest configuration: simulate an 8-device TPU-like mesh on CPU.

The reference has no unit-test suite at all -- its "tests" are runtime
verification scripts that need a real cluster (see SURVEY.md section 4,
/root/reference/tests/README.md). JAX lets us do better: on 8 virtual
CPU devices every sharding recipe (DP/FSDP/TP/PP/SP/ring/domain) is
unit-testable on a laptop.

The suite asks for that simulation BY NAME, once, here: the chip-path
entry points it drives in-process (bench.main, serve.server.main)
refuse any backend that is not a TPU unless TPU_HPC_SIM_DEVICES is set
(runtime.require_accelerator), and child processes inherit the
variable. Must be set before jax is imported anywhere; importing
tpu_hpc then puts the process on the CPU platform with 8 devices.
"""
import os
import sys

os.environ["TPU_HPC_SIM_DEVICES"] = "8"
# Keep CPU compilation deterministic and quiet in CI.
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tpu_hpc  # noqa: E402,F401  (reads TPU_HPC_SIM_DEVICES)
import jax  # noqa: E402
import pytest  # noqa: E402


# fast/slow split (VERDICT item 8): the tier-1 core must stay under
# ~10 minutes on a 2-core CPU host so it runs on every change; the
# full suite (no -m filter) is the round gate. This is the measured
# slowlist -- every entry's wall time (comment) comes from a full
# --durations=0 run on the CI-class container; together they cut the
# suite from ~32 min to ~10 min while the core keeps at least one
# cheap test on every subsystem. Durable coverage note: everything
# here still runs in the unfiltered suite.
SLOW_NODEIDS = frozenset(nodeid for nodeid, _ in [
    ("tests/test_autotune.py::test_bwd_tiling_is_numerics_invariant", "10s"),
    ("tests/test_ckpt.py::test_auto_resume_continues_from_step", "14s"),
    ("tests/test_ckpt.py::test_elastic_restore_onto_smaller_mesh", "13s"),
    ("tests/test_ckpt.py::test_mid_epoch_resume_stream_alignment", "19s"),
    ("tests/test_ckpt.py::test_restore_fp32_checkpoint_into_bf16_moments_run", "8s"),
    ("tests/test_ckpt.py::test_save_restore_roundtrip", "18s"),
    ("tests/test_doctor.py::TestAccumEscalation::test_accum_raised_until_fit", "27s"),
    ("tests/test_doctor.py::TestCandidates::test_cp_only_with_long_context", "48s"),
    ("tests/test_doctor.py::TestCandidates::test_gqa_head_divisibility", "58s"),
    ("tests/test_doctor.py::TestCandidates::test_meshes_are_legal", "17s"),
    ("tests/test_doctor.py::TestOutput::test_json_mode", "12s"),
    ("tests/test_doctor.py::TestOutput::test_no_fit_verdict", "87s"),
    ("tests/test_doctor.py::TestOutput::test_tight_marker", "13s"),
    ("tests/test_doctor.py::TestRanking::test_fitting_plans_rank_above_nonfitting", "74s"),
    ("tests/test_doctor.py::TestSlices::test_markdown_names_slices", "14s"),
    ("tests/test_doctor.py::TestSlices::test_slices_filter_and_dcn_cost", "14s"),
    ("tests/test_domain_unet.py::TestDomainUNet::test_param_grads_match", "11s"),
    ("tests/test_domain_unet.py::TestDomainUNet::test_train_forward_and_stats", "12s"),
    # test_eval's module-scoped ``trained`` fixture is a full fit
    # (~2 min); ANY fast-tier test in the module drags it into the
    # fast run, so the whole fixture family rides the slow tier.
    ("tests/test_eval.py::test_evaluate_returns_loss_and_accuracy", "105s"),
    ("tests/test_eval.py::test_evaluate_deterministic", "126s"),
    ("tests/test_eval.py::test_evaluate_matches_per_step_path", "5s"),
    ("tests/test_eval.py::test_evaluate_does_not_touch_state", "2s"),
    ("tests/test_eval.py::test_eval_forward_uses_inference_mode", "2s"),
    ("tests/test_eval.py::test_fit_with_eval_dataset_records_curve", "48s"),
    ("tests/test_fit.py::TestCPLayout::test_cp_step_compiles_on_sim_mesh", "16s"),
    ("tests/test_fit.py::test_model_presets", "10s"),
    ("tests/test_fit.py::test_sizing_table_rows_fit", "15s"),
    ("tests/test_fsdp_modes.py::TestHybridShard::test_matches_dp_numerics", "11s"),
    ("tests/test_fsdp_modes.py::TestShardGradOp::test_matches_full_shard_numerics", "13s"),
    ("tests/test_grad_clip.py::TestClipTraining::test_trains_and_is_accum_invariant", "10s"),
    ("tests/test_graft_entry.py::test_dryrun_multichip_in_process", "54s"),
    ("tests/test_pp.py::TestInterleaved::test_grads_match_oracle[interleaved-1f1b]", "13s"),
    ("tests/test_pp.py::TestInterleaved::test_grads_match_oracle[interleaved]", "15s"),
    ("tests/test_pp.py::TestInterleaved::test_indivisible_microbatches_still_correct[interleaved-1f1b]", "19s"),
    ("tests/test_pp.py::TestInterleaved::test_indivisible_microbatches_still_correct[interleaved]", "20s"),
    ("tests/test_pp.py::TestInterleaved::test_interleaved_1f1b_stash_grads_match_oracle", "15s"),
    ("tests/test_pp.py::TestInterleaved::test_interleaved_stash_wraparound_and_partial_group", "20s"),
    ("tests/test_pp.py::TestInterleaved::test_ppxdp_grads_match_oracle[interleaved-1f1b]", "10s"),
    ("tests/test_pp.py::TestInterleaved::test_ppxdp_grads_match_oracle[interleaved]", "15s"),
    ("tests/test_pp.py::TestStashBackward::test_grads_match_oracle", "12s"),
    ("tests/test_pp.py::TestStashBackward::test_ppxdp_grads_match_oracle", "13s"),
    ("tests/test_pp.py::TestStashBackward::test_stash_ring_wraparound", "9s"),
    # Pallas paged-attention sweep (tests/test_paged_kernels.py):
    # tier-1 keeps the (block_size=4, float32) representative per
    # kernel family; the rest of the block-size x dtype grid rides
    # the slow tier under the ``kernels`` marker.
    ("tests/test_paged_kernels.py::TestKernelSweep::test_decode_grid[4-bfloat16]", "1s"),
    ("tests/test_paged_kernels.py::TestKernelSweep::test_decode_grid[8-float32]", "1s"),
    ("tests/test_paged_kernels.py::TestKernelSweep::test_decode_grid[8-bfloat16]", "1s"),
    ("tests/test_paged_kernels.py::TestKernelSweep::test_prefill_grid[4-bfloat16]", "1s"),
    ("tests/test_paged_kernels.py::TestKernelSweep::test_prefill_grid[8-float32]", "1s"),
    ("tests/test_paged_kernels.py::TestKernelSweep::test_prefill_grid[8-bfloat16]", "1s"),
    ("tests/test_overlap.py::TestTrainerCommMode::test_bucketed_with_grad_accum_matches_flat", "10s"),
    ("tests/test_overlap.py::TestTrainerCommMode::test_flat_mode_no_collective_creep", "14s"),
    ("tests/test_pp.py::test_grads_match_oracle[1f1b]", "10s"),
    ("tests/test_precision.py::test_trainer_preserves_param_dtype_through_updates", "31s"),
    ("tests/test_precision.py::test_unet_vit_param_dtype_follows_config", "10s"),
    ("tests/test_profiling.py::test_window_triggering", "14s"),
    ("tests/test_resnet.py::test_forward_shape[50]", "14s"),
    ("tests/test_serve.py::TestReplayServerCLI::test_main_runs_replay_and_prints_summary", "8s"),
    ("tests/test_serve.py::TestServingWeights::test_trainer_checkpoint_restores_into_serving_layout", "9s"),
    # Speculative decoding (tests/test_spec.py): the tier-1 core keeps
    # one oracle test per draft source (ngram + independent draft),
    # the churn compile pin and the CLI guards; the heavier variants
    # (batch-composition determinism, self-draft accept-all,
    # draft-mode sampled determinism, loadgen determinism, drain
    # accounting, eos/prefix-hit long streams) ride the slow tier.
    ("tests/test_serve.py::TestSpecOracle::test_spec_greedy_token_exact_hit_and_miss[draft]", "9s"),
    ("tests/test_spec.py::TestGreedyOracle::test_self_draft_accepts_everything", "9s"),
    ("tests/test_spec.py::TestGreedyOracle::test_eos_mid_acceptance_truncates_exactly", "8s"),
    ("tests/test_spec.py::TestGreedyOracle::test_prefix_hit_and_long_stream_acceptance", "7s"),
    ("tests/test_spec.py::TestSeededSampling::test_seed_changes_the_stream", "9s"),
    ("tests/test_spec.py::TestSeededSampling::test_draft_mode_sampling_deterministic", "16s"),
    ("tests/test_spec.py::TestPageAccounting::test_pools_drain_to_idle_and_invariants_hold", "9s"),
    ("tests/test_spec.py::TestServerCLI::test_loadgen_with_spec_is_deterministic", "14s"),
    # Serving fleet (tests/test_fleet.py): the tier-1 core keeps one
    # fast representative per fault class (kill/redispatch, corrupt
    # swap, slow replica, scale-down drain) plus the diurnal
    # acceptance; the 8-combination chaos sweep rides the slow tier.
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[kill-affinity]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[kill-round_robin]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[slow-affinity]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[slow-round_robin]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[kill_slow-affinity]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[kill_slow-round_robin]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[corrupt_swap-affinity]", "3s"),
    ("tests/test_fleet.py::TestChaosSweep::test_sweep_no_loss_no_shed_above_floor[corrupt_swap-round_robin]", "3s"),
    # MPMD pipeline (tests/test_mpmd.py): the tier-1 core keeps the
    # chaos acceptance's two fault classes (stage kill + stage nan,
    # both bit-identity pinned), the parity/compile pins and the
    # budget units; the heartbeat-timeout / straggler variants and
    # the flapping-stage integration (each builds its own pipeline =
    # a full per-stage AOT warmup) ride the slow tier.
    ("tests/test_mpmd.py::TestHeartbeat::test_wedged_stage_detected_by_heartbeat_timeout", "8s"),
    ("tests/test_mpmd.py::TestStraggler::test_straggler_detected_and_bubble_grows", "7s"),
    ("tests/test_mpmd.py::TestBudgets::test_flapping_stage_exhausts_own_budget", "8s"),
    # Slice remap (elastic x MPMD): the remap chaos acceptance builds
    # TWO full pipelines (clean reference + storm) and the unfired-
    # fault guard a third; the cheap construction-time guard
    # (slice_up without slice_down) stays in the fast core. The SPMD
    # morph acceptance lives in tests/test_elastic.py, whose storm
    # fixture is module-scoped and stays fast.
    ("tests/test_mpmd.py::TestSliceRemap::test_slice_loss_remaps_without_burning_budget", "23s"),
    ("tests/test_mpmd.py::TestSliceRemap::test_unfired_slice_fault_fails_loudly", "6s"),
    ("tests/test_reshard.py::TestLongShapes::test_long_shape_bounded_parity_sweep", "35s"),
    # Wall-clock re-partition (elastic PR): the grown suite crossed
    # the tier-1 870s budget on the 1-core sim machine, so each
    # variant family below keeps its FASTEST representative in the
    # fast core and the heavier variants ride the slow tier -- every
    # behavior stays pinned somewhere, tier-1 stays inside its wall.
    ("tests/test_grad_accum.py::test_matches_full_batch_step[2]", "8s"),
    ("tests/test_pp.py::test_remat_stage_numerics_unchanged[interleaved-2]", "7s"),
    ("tests/test_pp.py::test_ppxdp_grads_match_oracle[1f1b]", "6s"),
    ("tests/test_pp_llama.py::test_interleaved_matches_sequential_oracle[interleaved-1f1b]", "8s"),
    ("tests/test_pp_llama.py::test_grads_match_sequential_oracle[gpipe-remat]", "7s"),
    ("tests/test_resnet.py::test_param_counts_match_torchvision", "8s"),
    ("tests/test_resnet.py::test_forward_shape[18]", "6s"),
    ("tests/test_spec.py::TestSeededSampling::test_batch_composition_invariance", "18s"),
    ("tests/test_doctor.py::TestRanking::test_sorted_best_first", "13s"),
    ("tests/test_ckpt.py::test_cross_layout_restore_fsdp_to_dp", "7s"),
    ("tests/test_precision.py::test_resnet_param_dtype_follows_config", "6s"),
    ("tests/test_resnet.py::test_fsdp_training_step", "60s"),
    ("tests/test_run_metrics.py::TestMetricsLog::test_appends_across_runs", "13s"),
    ("tests/test_runtime.py::TestHybridMesh::test_end_to_end_train_step_over_two_slices", "12s"),
    ("tests/test_sp.py::TestFSDPWithRing::test_fsdp_cp_trainer_bitexact_vs_replicated", "29s"),
    ("tests/test_sp.py::TestZigzagDataLayout::test_loss_and_grads_match_contiguous", "30s"),
    ("tests/test_train_dp.py::TestDPTraining::test_loss_decreases", "20s"),
    ("tests/test_train_dp.py::TestDPTraining::test_params_replicated", "9s"),
    ("tests/test_train_dp.py::TestFSDPTraining::test_fsdp_training_matches_dp", "20s"),
    ("tests/test_vision.py::TestBatchNormEvalRegression::test_eval_mode_tracks_train_mode", "68s"),
])


def pytest_collection_modifyitems(config, items):
    """fast/slow split: measured-heavy tests get the ``slow`` marker
    centrally (SLOW_NODEIDS above); everything else IS the fast core,
    marked so ``-m fast`` and ``-m 'not slow'`` select the same
    suite -- one partition, no test left in neither tier."""
    for item in items:
        if item.nodeid in SLOW_NODEIDS:
            item.add_marker(pytest.mark.slow)
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)
    # Self-maintenance: a renamed/re-parametrized slow test must not
    # silently drop into the fast tier. Checked per collected file so
    # single-file runs stay valid; skipped entirely for nodeid-level
    # selections or --deselect, where partial collection of a file is
    # expected (a single-test dev run must not abort on the file's
    # OTHER slowlist entries).
    if any("::" in a for a in config.args) or config.getoption(
        "deselect", None
    ):
        return
    present_files = {item.nodeid.split("::", 1)[0] for item in items}
    seen = {item.nodeid for item in items}
    stale = sorted(
        n for n in SLOW_NODEIDS
        if n.split("::", 1)[0] in present_files and n not in seen
    )
    if stale:
        raise pytest.UsageError(
            "conftest SLOW_NODEIDS entries match no collected test "
            f"(renamed? re-parametrized?): {stale}"
        )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    """1D 8-way data mesh."""
    from tpu_hpc.runtime import MeshSpec, build_mesh

    return build_mesh(MeshSpec(axes={"data": 8}))


@pytest.fixture(scope="session")
def mesh_2d(devices):
    """2D (data=2, model=4) mesh, the hybrid FSDPxTP shape."""
    from tpu_hpc.runtime import MeshSpec, build_mesh

    return build_mesh(MeshSpec(axes={"data": 2, "model": 4}))
