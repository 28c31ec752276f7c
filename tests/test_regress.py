"""obs/regress.py + obs/bank.py -- the perf-regression gate and the
banked-history converter.

Pure-host tests (no mesh): the gate's comparison semantics (direction
inference, tolerances, absolute SLO bounds), its pinned exit codes
(0 pass / 1 regression / 2 unusable input), and the --bank pipeline
over driver-style BENCH captures -- including the repo's own committed
BENCH_HISTORY.jsonl staying schema-valid.
"""
import json
import os

import pytest

from tpu_hpc.obs.bank import lift_capture, lift_file
from tpu_hpc.obs.bank import main as bank_main
from tpu_hpc.obs.regress import (
    bank_metrics,
    compare,
    lower_is_better,
    report_metrics,
)
from tpu_hpc.obs.regress import main as regress_main
from tpu_hpc.obs.schema import stamp, validate_file, validate_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------
# comparison semantics
# ---------------------------------------------------------------------
class TestCompare:
    def test_direction_inference(self):
        assert lower_is_better("serve.ttft_ms_p95")
        assert lower_is_better("loadgen.background.shed")
        assert lower_is_better("loadgen.stall_events")
        assert not lower_is_better("goodput")
        assert not lower_is_better("mfu")
        assert not lower_is_better("serve.tokens_per_s_per_chip")
        # Reshard-cost metrics: time, wire traffic, and transient peak
        # all regress UPWARD.
        assert lower_is_better("reshard_exchange_ms")
        assert lower_is_better("reshard_exchange_wire_bytes")
        assert lower_is_better("reshard.peak_inflight_bytes")
        # Paged KV cache efficiency (serve/paging.py): a DROPPING hit
        # rate and RISING block stalls are the regressions.
        assert not lower_is_better("serve.prefix_hit_rate")
        assert not lower_is_better("serve.kv_blocks_free_min")
        assert lower_is_better("serve.block_stalls")
        # Speculative decoding (serve/spec.py): acceptance_rate and
        # accepted regress by DROPPING; draft_ms and rejected by
        # RISING -- the --bank gate judges speculative rows instead
        # of skipping them.
        assert not lower_is_better("serve.acceptance_rate")
        assert not lower_is_better("loadgen_heavy_tail_accepted")
        assert lower_is_better("serve.draft_ms")
        assert lower_is_better("loadgen_heavy_tail_rejected")
        # Composite banked names take their direction from the LEAF:
        # an acceptance side key must not inherit the headline
        # latency metric's "ttft" token.
        assert not lower_is_better(
            "loadgen_x_paged_spec_ngram_ttft_ms_p95.acceptance_rate"
        )
        assert lower_is_better(
            "serve_spec_ngram_tokens_per_s_per_chip.itl_ms_p50"
        )
        # Elastic topology morphing (elastic/coordinator.py): stall
        # seconds, wire bytes, and morph counts all regress UPWARD --
        # a run that morphs more, moves more bytes, or stalls longer
        # for the same fault storm got worse.
        assert lower_is_better("elastic_morph_stall_s")
        assert lower_is_better("elastic_morph_stall_s.morphs")
        assert lower_is_better("elastic_morph_stall_s.morph_wire_bytes")
        assert lower_is_better("elastic.wire_bytes")
        assert lower_is_better("elastic.stall_s")
        # Host KV tier (serve/tier.py): pages thrashing across the
        # HBM/DRAM boundary, wire volume over the hop, and the
        # returning tenant's latency/shed all regress UPWARD;
        # resident_sessions (like prefix_hit_rate) regresses by
        # DROPPING -- higher-is-better by deliberate token absence.
        assert lower_is_better("serve.kv_spill_wire_bytes")
        assert lower_is_better("serve.kv_refill_wire_bytes")
        assert lower_is_better("serve.kv_hop_ms_p95")
        tiered = "loadgen_long_idle_sessions_paged_tiered_ttft_ms_p95"
        assert lower_is_better(tiered)
        assert lower_is_better(f"{tiered}.ttft_on_return_ms_p95")
        assert lower_is_better(f"{tiered}.shed_on_return")
        assert lower_is_better(f"{tiered}.kv_spill_wire_bytes")
        assert lower_is_better(f"{tiered}.kv_refill_wire_bytes")
        assert not lower_is_better(f"{tiered}.resident_sessions")
        # Live telemetry plane (obs/digest, obs/live, obs/slo): burn
        # pages, stale publishers, flagged stragglers, and the banked
        # sketch quantile error all regress UPWARD; slo_attainment and
        # budget_remaining regress by DROPPING -- higher-is-better by
        # deliberate token absence, like prefix_hit_rate.
        assert lower_is_better("slo.burns")
        assert lower_is_better("live.digest_stale")
        assert lower_is_better("live.stragglers")
        assert lower_is_better("obs.digest_quantile_rel_err")
        assert lower_is_better("obs.digest_publish_ms")
        assert not lower_is_better("slo.slo_attainment")
        assert not lower_is_better("slo.budget_remaining")
        # Quantized KV pages (kernels/paged_attention.py): the banked
        # logit_rmse pin regresses UPWARD -- a quantizer change that
        # widens the pre-softmax drift fails the gate even while the
        # latency headline rides within tolerance. Composite banked
        # names judge the rmse LEAF, and the kernel/quant family
        # suffixes keep the latency direction of their headline.
        assert lower_is_better("logit_rmse")
        assert lower_is_better(
            "loadgen_decode_heavy_paged_q8_ttft_ms_p95.logit_rmse"
        )
        assert lower_is_better(
            "loadgen_shared_prefix_paged_pallas_ttft_ms_p95"
        )
        assert lower_is_better(
            "loadgen_decode_heavy_paged_pallas_q8_ttft_ms_p95"
        )
        assert not lower_is_better(
            "serve_pallas_q8_tokens_per_s_per_chip"
        )

    def test_spec_config_fields_not_compared(self):
        """spec_k is config; drafted/accepted/rejected/verify_steps
        are raw workload-scaled counts (an IMPROVED acceptance rate
        means FEWER verify steps) -- the gate judges acceptance_rate
        and draft_ms only."""
        from tpu_hpc.obs.regress import report_metrics

        flat = report_metrics({
            "serve": {
                "spec_mode": "ngram", "spec_k": 4,
                "acceptance_rate": 0.9, "draft_ms": 2.5,
                "drafted": 100, "accepted": 90, "rejected": 10,
                "verify_steps": 30, "requests": 8,
            },
        })
        assert flat == {
            "serve.acceptance_rate": 0.9,
            "serve.draft_ms": 2.5,
        }

    def test_live_plane_flattening(self):
        """The report's live block flattens to the judged verdict
        counters (stale/straggler/burn counts, attainment, budget);
        the per-role tables and digest counts are identity detail
        the gate must not diff."""
        flat = report_metrics({
            "live": {
                "digests": 120, "digest_stale": 1,
                "stragglers": ["replica:2"], "slo_burns": 1,
                "slo_attainment": 0.93, "budget_remaining": -5.2,
                "roles": {"replica": {"keys": {}}},
            },
        })
        assert flat == {
            "live.digest_stale": 1.0,
            "live.stragglers": 1.0,
            "slo.burns": 1.0,
            "slo.slo_attainment": 0.93,
            "slo.budget_remaining": -5.2,
        }
        # None attainment (no SLO traffic): the optional leaves stay
        # absent instead of becoming NaN-ish zeros.
        flat = report_metrics({
            "live": {
                "digests": 3, "digest_stale": 0, "stragglers": [],
                "slo_burns": 0, "slo_attainment": None,
                "budget_remaining": None,
            },
        })
        assert flat == {
            "live.digest_stale": 0.0,
            "live.stragglers": 0.0,
            "slo.burns": 0.0,
        }

    def test_paged_config_fields_not_compared(self):
        """kv_block_size/kv_blocks (+free_min) are pool CONFIG, and
        prefill_chunks/raw hit counts DROP when the cache improves: a
        deliberate re-size or a better trie must not read as a perf
        regression -- the gate judges prefix_hit_rate and
        block_stalls only."""
        from tpu_hpc.obs.regress import report_metrics

        flat = report_metrics({
            "serve": {
                "prefix_hit_rate": 0.5, "kv_block_size": 16,
                "kv_blocks": 64, "kv_blocks_free_min": 3,
                "prefill_chunks": 9, "prefix_hits": 4,
                "prefix_hit_blocks": 12, "kv_layout": "paged",
                "block_stalls": 2, "requests": 8,
            },
        })
        assert flat == {
            "serve.prefix_hit_rate": 0.5,
            "serve.block_stalls": 2.0,
        }

    def test_tier_config_fields_not_compared(self):
        """kv_host_blocks/inflight are tier CONFIG, used/free follow
        it, and the spill/refill EVENT counts scale with workload --
        the gate judges the wire bytes and the hop quantiles only."""
        from tpu_hpc.obs.regress import report_metrics

        flat = report_metrics({
            "serve": {
                "kv_host_blocks": 64, "kv_host_used": 10,
                "kv_host_free": 53, "kv_host_drops": 1,
                "kv_host_inflight_bytes": 1 << 20,
                "kv_spills": 3, "kv_spill_pages": 12,
                "kv_refills": 2, "kv_refill_pages": 8,
                "kv_spill_wire_bytes": 4096.0,
                "kv_refill_wire_bytes": 2048.0,
                "kv_hop_ms_p50": 0.4, "kv_hop_ms_p95": 0.9,
                "requests": 8,
            },
        })
        assert flat == {
            "serve.kv_spill_wire_bytes": 4096.0,
            "serve.kv_refill_wire_bytes": 2048.0,
            "serve.kv_hop_ms_p50": 0.4,
            "serve.kv_hop_ms_p95": 0.9,
        }

    def test_identical_passes(self):
        m = {"serve.ttft_ms_p95": 10.0, "goodput": 0.9}
        violations, checked = compare(m, dict(m))
        assert violations == [] and checked == 2

    def test_latency_inflation_fails_with_name(self):
        base = {"serve.ttft_ms_p95": 10.0}
        cand = {"serve.ttft_ms_p95": 15.0}
        violations, _ = compare(base, cand)
        assert len(violations) == 1
        v = violations[0]
        assert v["metric"] == "serve.ttft_ms_p95"
        assert v["direction"] == "lower"

    def test_throughput_drop_fails_improvement_passes(self):
        base = {"mfu": 0.50}
        assert compare(base, {"mfu": 0.40})[0]
        assert compare(base, {"mfu": 0.60})[0] == []
        # 10% default tolerance: a 5% dip rides
        assert compare(base, {"mfu": 0.475})[0] == []

    def test_tolerance_overrides(self):
        base = {"serve.ttft_ms_p95": 100.0}
        cand = {"serve.ttft_ms_p95": 107.0}
        assert compare(base, cand, tol=0.10)[0] == []
        assert compare(base, cand, tol=0.05)[0]
        slo = {"metrics": {"serve.ttft_ms_p95": {"tol": 0.02}}}
        assert compare(base, cand, slo=slo, tol=0.10)[0]
        slo = {"default_tol": 0.02}
        assert compare(base, cand, slo=slo, tol=0.10)[0]

    def test_absolute_slo_bounds_apply_to_candidate_alone(self):
        # Baseline already over the bound: the relative check passes
        # but the SLO still fires -- SLOs are absolute promises.
        slo = {"metrics": {"serve.ttft_ms_p95": {"max": 200.0},
                           "goodput": {"min": 0.8}}}
        base = {"serve.ttft_ms_p95": 300.0, "goodput": 0.5}
        cand = {"serve.ttft_ms_p95": 290.0, "goodput": 0.55}
        violations, _ = compare(base, cand, slo=slo)
        kinds = {v["metric"]: v["kind"] for v in violations}
        assert kinds == {"serve.ttft_ms_p95": "slo_max",
                         "goodput": "slo_min"}

    def test_one_sided_metrics_skipped(self):
        violations, checked = compare(
            {"old_metric": 1.0}, {"new_metric": 2.0}
        )
        assert violations == [] and checked == 0

    def test_passing_slo_bounds_count_as_checks(self):
        """Review finding: an SLO-only gate (no overlapping baseline
        metrics) whose absolute bounds all PASS must count its checks
        -- checked == 0 would turn a healthy run into exit 2."""
        slo = {"metrics": {
            "serve.ttft_ms_p95": {"max": 200.0},
            "goodput": {"min": 0.5, "max": 1.0},
        }}
        violations, checked = compare(
            {}, {"serve.ttft_ms_p95": 50.0, "goodput": 0.9}, slo=slo,
        )
        assert violations == []
        assert checked == 3  # one max + one min + one max, all pass

    def test_bound_on_missing_metric_is_a_violation(self):
        """Review finding: an absolute SLO bound naming a metric the
        candidate never produced (typo, wrong run type) must fail the
        gate, not silently never fire. tol-only entries stay quiet --
        they are modifiers for the relative pass, not promises."""
        slo = {"metrics": {
            "serve.ttft_ms_95": {"max": 200.0},        # typoed p95
            "serve.ttft_ms_p95": {"tol": 0.05},        # tol-only: ok
        }}
        violations, checked = compare(
            {"goodput": 0.9}, {"goodput": 0.9}, slo=slo,
        )
        assert checked == 2  # goodput relative + the missing bound
        assert len(violations) == 1
        assert violations[0]["kind"] == "slo_missing"
        assert violations[0]["metric"] == "serve.ttft_ms_95"


# ---------------------------------------------------------------------
# report flattening
# ---------------------------------------------------------------------
class TestReportMetrics:
    def test_flattens_all_sections(self):
        rep = {
            "goodput": {"combined": {"goodput": 0.9}},
            "mfu": {"mfu": 0.5},
            "serve": {"ttft_ms_p95": 12.0, "tokens_per_s": 100.0,
                      "requests": 8},
            "loadgen": {
                "tenants": {
                    "bg": {"ttft_ms_p50": 1.0, "ttft_ms_p95": 2.0,
                           "ttft_ms_p99": 3.0, "itl_ms_p50": 0.5,
                           "itl_ms_p95": 0.8, "shed": 4,
                           "queued": 6},
                },
                "occupancy_mean": 0.7,
                "stall_events": 2,
                "shed": 4,
            },
        }
        flat = report_metrics(rep)
        assert flat["goodput"] == 0.9
        assert flat["mfu"] == 0.5
        assert flat["serve.ttft_ms_p95"] == 12.0
        assert "serve.requests" not in flat  # workload size, not perf
        assert flat["loadgen.bg.ttft_ms_p95"] == 2.0
        assert flat["loadgen.bg.itl_ms_p95"] == 0.8
        assert flat["loadgen.bg.shed"] == 4.0
        # Per-tenant queued IS gated (docs promise it): shifting
        # queueing between classes at constant total must not pass.
        assert flat["loadgen.bg.queued"] == 6.0
        assert flat["loadgen.occupancy_mean"] == 0.7
        assert flat["loadgen.stall_events"] == 2.0

    def test_missing_sections_tolerated(self):
        assert report_metrics({"goodput": None, "mfu": None,
                               "serve": None, "loadgen": None}) == {}


# ---------------------------------------------------------------------
# CLI exit codes (pinned)
# ---------------------------------------------------------------------
def _write_run(path, ttft_p95=10.0, ttft_p99=12.0):
    """A minimal schema-valid serve run: one summary record."""
    rec = stamp({
        "event": "serve_summary",
        "requests": 4, "tokens": 16, "wall_s": 1.0,
        "tokens_per_s": 16.0, "tokens_per_s_per_chip": 2.0,
        "ttft_ms_p50": 5.0, "ttft_ms_p95": ttft_p95,
        "ttft_ms_p99": ttft_p99,
        "itl_ms_p50": 1.0, "itl_ms_p95": 2.0, "prefill_tokens": 32,
    })
    validate_record(rec)
    path.write_text(json.dumps(rec) + "\n")


class TestCLI:
    def test_pass_fail_exit_codes(self, tmp_path, capsys):
        a, b, c = (tmp_path / f"{x}.jsonl" for x in "abc")
        _write_run(a)
        _write_run(b)
        _write_run(c, ttft_p95=20.0)
        assert regress_main([str(a), str(b)]) == 0
        assert regress_main([str(a), str(c)]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION: serve.ttft_ms_p95" in out

    def test_unusable_input_is_2(self, tmp_path, capsys):
        good = tmp_path / "good.jsonl"
        _write_run(good)
        missing = tmp_path / "gone.jsonl"
        assert regress_main([str(good), str(missing)]) == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert regress_main([str(good), str(empty)]) == 2
        invalid = tmp_path / "bad.jsonl"
        invalid.write_text('{"event": "mystery"}\n')
        assert regress_main([str(good), str(invalid)]) == 2
        capsys.readouterr()

    def test_nothing_to_compare_is_2(self, tmp_path, capsys):
        """A gate with zero comparable metrics must fail loudly, not
        pass vacuously."""
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        # schema-valid but metric-free records
        rec = stamp({"event": "fault", "kind": "kill"})
        a.write_text(json.dumps(rec) + "\n")
        b.write_text(json.dumps(rec) + "\n")
        assert regress_main([str(a), str(b)]) == 2
        capsys.readouterr()

    def test_json_verdict(self, tmp_path, capsys):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        _write_run(a)
        _write_run(b, ttft_p95=20.0, ttft_p99=30.0)
        assert regress_main([str(a), str(b), "--json"]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["pass"] is False
        assert verdict["schema_version"] == 1
        named = {v["metric"] for v in verdict["violations"]}
        assert named == {"serve.ttft_ms_p95", "serve.ttft_ms_p99"}

    def test_slo_config_file(self, tmp_path, capsys):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        _write_run(a)
        _write_run(b, ttft_p95=10.5)
        slo = tmp_path / "slo.json"
        slo.write_text(json.dumps({
            "metrics": {"serve.ttft_ms_p95": {"tol": 0.01}}
        }))
        assert regress_main([str(a), str(b)]) == 0
        assert regress_main([str(a), str(b), "--slo", str(slo)]) == 1
        capsys.readouterr()


# ---------------------------------------------------------------------
# the bank: converter + --bank mode
# ---------------------------------------------------------------------
def _capture(n, rc, parsed, tail=""):
    return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": tail,
            "parsed": parsed}


class TestBank:
    def test_lift_success_and_failure(self):
        ok = lift_capture(_capture(
            1, 0,
            {"metric": "m", "value": 10.0, "unit": "tok/s",
             "vs_baseline": 1.0},
            tail="llama bench | MFU 46.3% (peak)",
        ), "BENCH_r01.json")
        validate_record(ok)
        assert ok["value"] == 10.0 and ok["round"] == 1
        assert ok["mfu"] == pytest.approx(0.463)
        bad = lift_capture(
            _capture(2, 3, None, tail="probe failed\nbackend down"),
            "BENCH_r02.json",
        )
        validate_record(bad)
        assert bad["value"] is None and bad["unit"] == "FAILED"
        assert bad["error"] == "backend down"

    def test_cli_writes_validated_history(self, tmp_path, capsys):
        src = tmp_path / "BENCH_r01.json"
        src.write_text(json.dumps(_capture(
            1, 0, {"metric": "m", "value": 5.0, "unit": "u"},
        )))
        rows = tmp_path / "extra.jsonl"
        rows.write_text(json.dumps(
            {"metric": "m2", "value": 7.0, "unit": "u",
             "workload": "x"}
        ) + "\n")
        out = tmp_path / "HIST.jsonl"
        assert bank_main([str(src), str(rows), "-o", str(out)]) == 0
        assert validate_file(str(out)) == 2
        capsys.readouterr()

    def test_cli_rejects_garbage(self, tmp_path, capsys):
        bad = tmp_path / "junk.json"
        bad.write_text(json.dumps({"whatever": 1}))
        assert bank_main([str(bad), "-o", str(tmp_path / "o")]) == 2
        capsys.readouterr()

    def test_bank_lifts_acceptance_rate_side_key(self):
        """acceptance_rate is a banked side key: a speculative row's
        mechanism metric rides the gate next to its latency
        quantiles (higher-is-better), so a stale draft fails --bank
        even when ttft/itl still ride within tolerance."""
        from tpu_hpc.obs.regress import bank_metrics, compare

        def row(acc):
            return {
                "event": "bench",
                "metric": "loadgen_x_paged_spec_ngram_ttft_ms_p95",
                "value": 100.0, "acceptance_rate": acc,
            }

        base = bank_metrics([row(0.9)])
        key = "loadgen_x_paged_spec_ngram_ttft_ms_p95.acceptance_rate"
        assert base[key] == 0.9
        violations, _ = compare(base, bank_metrics([row(0.5)]))
        assert [v["metric"] for v in violations] == [key]
        assert compare(base, bank_metrics([row(0.95)]))[0] == []

    def test_bank_lifts_tier_side_keys(self):
        """The host-tier row's mechanism metrics are banked side
        keys: TTFT-on-return and shed_on_return (lower), spill/refill
        wire bytes (lower), resident_sessions (higher) ride the
        --bank gate next to the tiered latency headline -- a tier
        that starts shedding returns or thrashing pages fails even
        while p95 TTFT holds."""
        from tpu_hpc.obs.regress import bank_metrics, compare

        name = "loadgen_long_idle_sessions_paged_tiered_ttft_ms_p95"

        def row(ret_p95=40.0, shed=0, resident=20, spill=4096.0):
            return {
                "event": "bench", "metric": name, "value": 100.0,
                "ttft_on_return_ms_p50": 20.0,
                "ttft_on_return_ms_p95": ret_p95,
                "shed_on_return": shed,
                "resident_sessions": resident,
                "kv_spill_wire_bytes": spill,
                "kv_refill_wire_bytes": spill / 2,
            }

        base = bank_metrics([row()])
        for key in (
            "ttft_on_return_ms_p50", "ttft_on_return_ms_p95",
            "shed_on_return", "resident_sessions",
            "kv_spill_wire_bytes", "kv_refill_wire_bytes",
        ):
            assert f"{name}.{key}" in base, key
        assert compare(base, bank_metrics([row()]))[0] == []
        for bad, key in (
            (row(ret_p95=80.0), "ttft_on_return_ms_p95"),
            (row(shed=5), "shed_on_return"),
            (row(resident=2), "resident_sessions"),
            (row(spill=40960.0), "kv_spill_wire_bytes"),
        ):
            violations, _ = compare(base, bank_metrics([bad]))
            assert f"{name}.{key}" in [
                v["metric"] for v in violations
            ], key

    def test_bank_metrics_keep_high_water_mark(self):
        records = [
            stamp({"event": "bench", "metric": "tok_per_chip",
                   "value": v, "unit": "tok/s"})
            for v in (100.0, 120.0, None, 110.0)
        ]
        records.append(stamp({
            "event": "bench", "metric": "serve_tps",
            "value": 50.0, "unit": "tok/s",
            "ttft_ms_p95": 40.0,
        }))
        records.append(stamp({
            "event": "bench", "metric": "serve_tps",
            "value": 45.0, "unit": "tok/s",
            "ttft_ms_p95": 30.0,
        }))
        best = bank_metrics(records)
        assert best["tok_per_chip"] == 120.0          # max (higher)
        assert best["serve_tps"] == 50.0
        assert best["serve_tps.ttft_ms_p95"] == 30.0  # min (lower)

    def test_bank_mode_gates_candidate(self, tmp_path, capsys):
        bank = tmp_path / "hist.jsonl"
        bank.write_text("\n".join(json.dumps(stamp({
            "event": "bench", "metric": "tok_per_chip",
            "value": v, "unit": "tok/s",
        })) for v in (100.0, 120.0)) + "\n")
        good = tmp_path / "good.jsonl"
        good.write_text(json.dumps(stamp({
            "event": "bench", "metric": "tok_per_chip",
            "value": 118.0, "unit": "tok/s",
        })) + "\n")
        slow = tmp_path / "slow.jsonl"
        slow.write_text(json.dumps(stamp({
            "event": "bench", "metric": "tok_per_chip",
            "value": 90.0, "unit": "tok/s",
        })) + "\n")
        assert regress_main(
            ["--bank", str(bank), str(good)]
        ) == 0
        assert regress_main(
            ["--bank", str(bank), str(slow)]
        ) == 1
        out = capsys.readouterr().out
        assert "tok_per_chip" in out

    def test_bank_candidate_judged_by_latest_not_best(
        self, tmp_path, capsys,
    ):
        """Review finding: a candidate file holding several rounds
        must be judged by its NEWEST record per metric -- a regressed
        latest round must not hide behind a better earlier row."""
        bank = tmp_path / "hist.jsonl"
        bank.write_text(json.dumps(stamp({
            "event": "bench", "metric": "tok_per_chip",
            "value": 56.0, "unit": "tok/s",
        })) + "\n")
        cand = tmp_path / "cand.jsonl"
        cand.write_text("\n".join(json.dumps(stamp({
            "event": "bench", "metric": "tok_per_chip",
            "value": v, "unit": "tok/s",
        })) for v in (57.0, 50.0)) + "\n")  # newest round regressed
        assert regress_main(["--bank", str(bank), str(cand)]) == 1
        assert "tok_per_chip" in capsys.readouterr().out
        # The bank (baseline) side still keeps the high-water mark.
        assert bank_metrics([json.loads(l) for l in
                             cand.read_text().splitlines()],
                            keep="best")["tok_per_chip"] == 57.0

    def test_reshard_cost_regression_fails_the_bank_diff(
        self, tmp_path, capsys,
    ):
        """Satellite pin: comm/bench.py's reshard rows ride the bank
        gate -- a slower execute OR more wire bytes than the banked
        history fails with the metric named (both are lower-is-better
        by the direction tokens)."""
        def rows(ms, wire):
            return [
                stamp({
                    "event": "bench", "metric": "reshard_exchange_ms",
                    "value": ms, "unit": "ms", "op": "reshard_exchange",
                }),
                stamp({
                    "event": "bench",
                    "metric": "reshard_exchange_wire_bytes",
                    "value": wire, "unit": "bytes",
                    "op": "reshard_exchange",
                }),
            ]

        def write(path, recs):
            path.write_text(
                "\n".join(json.dumps(r) for r in recs) + "\n"
            )
            return str(path)

        bank = write(tmp_path / "hist.jsonl", rows(2.0, 28000))
        ok = write(tmp_path / "ok.jsonl", rows(2.1, 28000))
        slow = write(tmp_path / "slow.jsonl", rows(4.0, 28000))
        fat = write(tmp_path / "fat.jsonl", rows(2.0, 60000))
        assert regress_main(["--bank", bank, ok]) == 0
        assert regress_main(["--bank", bank, slow]) == 1
        assert "reshard_exchange_ms" in capsys.readouterr().out
        assert regress_main(["--bank", bank, fat]) == 1
        assert "reshard_exchange_wire_bytes" in (
            capsys.readouterr().out
        )

    def test_live_reshard_bench_rows_ride_the_gate(self, tmp_path):
        """End to end: real run_reshard_bench rows on the sim mesh are
        schema-valid JSONL the bank gate accepts (exit 0 against
        themselves)."""
        import jax

        from tpu_hpc.comm.bench import run_reshard_bench
        from tpu_hpc.runtime import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(axes={"data": jax.device_count()}))
        records = run_reshard_bench(
            mesh, sizes=[256], warmup=0, iters=1,
            ops=("reshard_exchange",),
        )
        assert records
        path = tmp_path / "rs.jsonl"
        path.write_text(
            "\n".join(json.dumps(r) for r in records) + "\n"
        )
        assert validate_file(str(path)) == len(records)
        assert regress_main(
            ["--bank", str(path), str(path), "--tol", "0.5"]
        ) == 0

    def test_committed_history_artifact_is_valid(self):
        """The repo's own BENCH_HISTORY.jsonl (the bank `regress
        --bank` trusts) stays schema-valid and keeps the trajectory's
        known high-water marks."""
        path = os.path.join(REPO, "BENCH_HISTORY.jsonl")
        assert os.path.exists(path), "run python -m tpu_hpc.obs.bank"
        assert validate_file(path) > 0
        from tpu_hpc.obs.schema import load_records

        best = bank_metrics(load_records(path))
        # Rebuilt (python -m tpu_hpc.obs.bank) from the row files the
        # repo still holds; the training family's mark is the
        # BENCH_EXTRA.jsonl sweep row.
        assert best["llama2_train_tokens_per_s_per_chip"] == \
            pytest.approx(121363.1)
        # No driver capture is banked any more, so no row carries the
        # lifted ``mfu`` extra.
        assert "llama2_train_tokens_per_s_per_chip.mfu" not in best
        # Every banked row names the row file it was lifted from.
        assert all(r.get("source") for r in load_records(path))
