"""Roofline estimator: pinned against the bench model's step budget.

The budget in docs/guide/xla_performance_notes.md (76 ms step, 50%
MFU, pure-matmul bound ~38 ms at the bench model on one v5e) is what
the estimator must bracket: a lower bound above the step it bounds, or
a ceiling below an achieved utilisation, is a broken roofline. The
budget dates from an earlier round; it has not been re-measured on the
chip in this one."""
import pytest

from tpu_hpc.checks import roofline
from tpu_hpc.models import llama2

BENCH = llama2.LlamaConfig(
    dim=1024, n_layers=8, n_heads=8, vocab_size=32000,
    multiple_of=256, max_seq_len=2048,
)


def test_single_chip_brackets_the_measured_step():
    r = roofline.estimate(BENCH, chip="v5e", global_batch=4)
    # Matmul lower bound ~38 ms (xla_performance_notes.md budget).
    assert 35 < r.compute_s * 1e3 < 41
    assert r.bound == "compute"
    # Budget: 76 ms -> the bound must be below it, and the budget's
    # 50% MFU must not exceed the estimator's ceiling.
    assert r.step_time_lower_bound_s < 0.076
    assert r.mfu_upper_bound >= 0.50


def test_comm_bytes_invariant_under_grad_accum():
    """Accumulation splits the same rows into microbatches; total TP
    collective bytes per step must not change (regression: an early
    version multiplied whole-batch bytes by the accum factor)."""
    a1 = roofline.estimate(
        llama2.PRESETS["7b"], chip="v5e", dp=4, axis2=8,
        global_batch=32, seq_len=4096, grad_accum=1,
    )
    a8 = roofline.estimate(
        llama2.PRESETS["7b"], chip="v5e", dp=4, axis2=8,
        global_batch=32, seq_len=4096, grad_accum=8,
    )
    assert a1.comm_breakdown["tp_model_axis"] == pytest.approx(
        a8.comm_breakdown["tp_model_axis"]
    )
    # Param re-reads DO scale with accum (each microbatch re-reads).
    assert (
        a8.memory_breakdown["param_reads"]
        > a1.memory_breakdown["param_reads"]
    )


def test_layouts_emit_their_own_comm_terms():
    tp = roofline.estimate(
        llama2.PRESETS["7b"], chip="v5e", dp=2, axis2=4,
        layout="tp", global_batch=8, seq_len=4096,
    )
    cp = roofline.estimate(
        llama2.PRESETS["7b"], chip="v5e", dp=2, axis2=4,
        layout="cp", global_batch=8, seq_len=4096,
    )
    assert "tp_model_axis" in tp.comm_breakdown
    assert "kv_ring_context_axis" in cp.comm_breakdown
    assert "fsdp_data_axis" in tp.comm_breakdown
    # GQA makes the KV ring far cheaper than SP's residual reductions.
    assert (
        cp.comm_breakdown["kv_ring_context_axis"]
        < tp.comm_breakdown["tp_model_axis"]
    )


def test_bf16_moments_shrink_memory_bound():
    f32 = roofline.estimate(BENCH, chip="v5e", global_batch=4)
    bf16 = roofline.estimate(
        BENCH, chip="v5e", global_batch=4, moments_dtype="bfloat16"
    )
    assert bf16.memory_s < f32.memory_s


def test_bound_is_max_of_components():
    r = roofline.estimate(
        llama2.PRESETS["7b"], chip="v5e", dp=4, axis2=8,
        global_batch=32, seq_len=4096,
    )
    assert r.step_time_lower_bound_s == max(
        r.compute_s, r.memory_s, r.comm_s
    )
    assert 0 < r.mfu_upper_bound <= 1.0


def test_cli_json(capsys):
    roofline.main([
        "--model", "7b", "--chip", "v5e", "--dp", "4", "--tp", "8",
        "--global-batch", "32", "--seq-len", "4096", "--json",
    ])
    import json

    out = json.loads(capsys.readouterr().out)
    assert out["bound"] in ("compute", "memory", "comm")
    assert out["step_time_lower_bound_ms"] > 0


def test_estimate_accepts_chip_spec_instance():
    # A ChipSpec (e.g. host-calibrated measured rates) can replace the
    # CHIPS-key lookup; derated rates must move the bounds accordingly.
    spec = roofline.CHIPS["v5e"]
    import dataclasses
    derated = dataclasses.replace(
        spec, name="v5e-measured",
        peak_bf16_flops=spec.peak_bf16_flops * 0.5,
        hbm_gbps=spec.hbm_gbps * 0.5,
    )
    base = roofline.estimate(BENCH, chip=spec, global_batch=4)
    slow = roofline.estimate(BENCH, chip=derated, global_batch=4)
    assert slow.compute_s == pytest.approx(2 * base.compute_s)
    assert slow.memory_s == pytest.approx(2 * base.memory_s)
    assert slow.chip.name == "v5e-measured"


def test_measured_chip_spec_substitutes_microbench_rates(monkeypatch):
    # The calibration path swaps in the microbench's measured matmul
    # and HBM rates, keeps spec ICI/capacity, and tags the name --
    # verified against fixed fake rates (the real microbench needs a
    # real chip).
    from tpu_hpc.checks import env_check

    monkeypatch.setattr(
        env_check, "chip_microbench",
        lambda: {"matmul_tflops": 192.0, "hbm_gb_s": 657.0},
    )
    spec = roofline.measured_chip_spec(roofline.CHIPS["v5e"])
    assert spec.name == "v5e-measured"
    assert spec.peak_bf16_flops == pytest.approx(192.0e12)
    assert spec.hbm_gbps == pytest.approx(657.0)
    assert spec.ici_gbps == roofline.CHIPS["v5e"].ici_gbps
    assert spec.hbm_gib == roofline.CHIPS["v5e"].hbm_gib


class TestPPLayout:
    """Pipeline roofline: schedule_factor carries bubble + remat."""

    def test_schedule_factor_exact(self):
        # 4 stages, 8 microbatches: bubble stretch (8+3)/8; the
        # default remat backward costs 5/3 in fwd-units (loss forward
        # + combined-program fwd slot + vjp recompute), the stash
        # backward 4/3 (residuals saved at forward time).
        r = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=8, grad_accum=8,
        )
        assert r.layout == "pp"
        assert r.schedule_factor == pytest.approx((11 / 8) * (5 / 3))
        stash = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=8, grad_accum=8, pp_backward="stash",
        )
        assert stash.schedule_factor == pytest.approx((11 / 8) * (4 / 3))
        # MFU ceiling is depressed by exactly the schedule factor when
        # the schedule term binds.
        if r.bound == "schedule":
            assert r.mfu_upper_bound == pytest.approx(
                1 / r.schedule_factor
            )

    def test_more_microbatches_shrink_bubble(self):
        r8 = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=8, grad_accum=8,
        )
        r32 = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=32, grad_accum=32,
        )
        assert r32.schedule_factor < r8.schedule_factor

    def test_stage_hops_and_ddp_terms(self):
        r = roofline.estimate(
            BENCH, dp=2, axis2=4, layout="pp",
            global_batch=16, grad_accum=8,
        )
        assert "pp_stage_hops" in r.comm_breakdown
        assert "ddp_grad_allreduce" in r.comm_breakdown

    def test_stash_pays_memory_for_its_flops(self):
        # Stash lowers the schedule factor but adds residual traffic:
        # the roofline must not present it as strictly free.
        remat = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=8, grad_accum=8,
        )
        stash = roofline.estimate(
            BENCH, dp=1, axis2=4, layout="pp",
            global_batch=8, grad_accum=8, pp_backward="stash",
        )
        assert stash.schedule_factor < remat.schedule_factor
        assert stash.memory_s > remat.memory_s
        assert "stash_residuals" in stash.memory_breakdown
        assert "stash_residuals" not in remat.memory_breakdown

    def test_layers_must_divide_stages(self):
        with pytest.raises(ValueError, match="divisible by"):
            roofline.estimate(
                BENCH, dp=1, axis2=3, layout="pp",
                global_batch=6, grad_accum=6,
            )


class TestSlices:
    """Multi-slice data axis: the cross-slice phase rides DCN."""

    def test_dcn_binds_when_slow(self):
        import dataclasses as dc

        # A chip with near-zero DCN share: two slices must slow the
        # FSDP axis vs one; single-slice result must be unchanged.
        slow_dcn = dc.replace(
            roofline.CHIPS["v5e"], name="slow-dcn", dcn_gbps=0.1
        )
        one = roofline.estimate(
            BENCH, chip=slow_dcn, dp=8, global_batch=16, slices=1
        )
        two = roofline.estimate(
            BENCH, chip=slow_dcn, dp=8, global_batch=16, slices=2
        )
        assert two.comm_breakdown["fsdp_data_axis"] > \
            one.comm_breakdown["fsdp_data_axis"]
        assert two.slices == 2

    def test_slices_must_divide_dp(self):
        with pytest.raises(ValueError, match="divisible by slices"):
            roofline.estimate(
                BENCH, dp=3, global_batch=6, slices=2
            )

