"""tpu_hpc.obs -- the unified telemetry spine.

Covers the spine itself (event bus + flight recorder, spans, metrics
registry, stall watermark, schema, report CLI) and its integration
acceptance runs: a sim-mesh training run whose JSONL validates against
the shared schema and yields a goodput/MFU/step-time report, and a
faulted run (TPU_HPC_FAULTS) that leaves a flight-recorder dump of the
last pre-fault events.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tpu_hpc import obs
from tpu_hpc.obs.registry import MetricsRegistry
from tpu_hpc.obs.report import build_report, format_report
from tpu_hpc.obs.report import main as report_main
from tpu_hpc.obs.schema import (
    SCHEMA_VERSION,
    SchemaError,
    stamp,
    validate_file,
    validate_record,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bus(tmp_path):
    """A scoped process bus (file sink + flight dir in tmp), restored
    afterwards so the singleton never leaks between tests."""
    b = obs.EventBus(
        path=str(tmp_path / "events.jsonl"), run_id="test-run",
        ring_size=8, flight_dir=str(tmp_path),
    )
    prev = obs.set_bus(b)
    yield b
    obs.set_bus(prev)


@pytest.fixture()
def registry():
    """A scoped process registry, restored afterwards."""
    r = MetricsRegistry(hist_window=4)
    prev = obs.set_registry(r)
    yield r
    obs.set_registry(prev)


# ---------------------------------------------------------------------
# events.py: bus + flight recorder
# ---------------------------------------------------------------------
class TestEventBus:
    def test_emit_stamps_and_sinks(self, bus):
        rec = bus.emit("fault", kind="kill", step=3)
        assert rec["schema_version"] == SCHEMA_VERSION
        assert rec["run_id"] == "test-run"
        assert rec["host"] and rec["pid"] == os.getpid()
        assert rec["time"] > 0
        on_disk = [json.loads(x) for x in open(bus.path)]
        assert on_disk == [rec]
        validate_file(bus.path)

    def test_none_fields_dropped(self, bus):
        rec = bus.emit("fault", kind="stall", step=None)
        assert "step" not in rec

    def test_ring_is_bounded(self, bus):
        for i in range(20):
            bus.emit("fault", kind="kill", step=i)
        ring = list(bus.ring())
        assert len(ring) == 8  # ring_size
        assert [r["step"] for r in ring] == list(range(12, 20))

    def test_same_file_as_path_and_sink_written_once(self, bus):
        bus.emit("fault", kind="kill", sink=bus.path)
        assert len(open(bus.path).readlines()) == 1

    def test_dump_flight_header_and_events(self, bus, tmp_path):
        bus.emit("fault", kind="kill", step=1)
        path = bus.dump_flight("preempt")
        assert path and os.path.dirname(path) == str(tmp_path)
        recs = [json.loads(x) for x in open(path)]
        assert recs[0]["event"] == "flight_dump"
        assert recs[0]["reason"] == "preempt"
        assert recs[0]["n_events"] == 1
        assert recs[1]["event"] == "fault"
        validate_file(path)

    def test_dump_never_clobbers(self, bus):
        first = bus.dump_flight("hang")
        second = bus.dump_flight("hang")
        assert second != first and os.path.exists(first)

    def test_dump_without_destination_is_noop(self):
        b = obs.EventBus(flight_dir=None)
        assert b.dump_flight("preempt") is None

    def test_empty_string_paths_mean_off(self, tmp_path, monkeypatch):
        """'' is the documented off spelling (metrics_path='') and a
        set-but-empty env var must disable, not crash, every emit
        (review finding)."""
        monkeypatch.chdir(tmp_path)
        b = obs.EventBus(path="", flight_dir="")
        b.emit("fault", kind="kill", sink="")
        assert b.dump_flight("preempt") is None
        assert list(tmp_path.iterdir()) == []

    def test_module_level_dump_uses_current_bus(self, bus):
        bus.emit("fault", kind="kill")
        path = obs.dump_flight("kill")
        assert path and "kill" in os.path.basename(path)

    def test_fault_announce_is_one_shot(self, bus):
        """A ``step >= N`` fault match re-fires every later chunk;
        the telemetry event must not (review finding)."""
        from tpu_hpc.resilience.faults import FaultPlan

        plan = FaultPlan(stall_at_step=2, stall_s=0.0)
        for step in (2, 3, 4):
            plan.on_step(step)
        stalls = [
            r for r in bus.ring()
            if r["event"] == "fault" and r["kind"] == "stall"
        ]
        assert len(stalls) == 1 and stalls[0]["step"] == 2


# ---------------------------------------------------------------------
# spans.py
# ---------------------------------------------------------------------
class TestSpans:
    def test_nesting_records_parent_and_depth(self, bus):
        with obs.span("outer", annotate=False):
            with obs.span("inner", annotate=False):
                pass
        recs = [json.loads(x) for x in open(bus.path)]
        by = {r["name"]: r for r in recs}
        assert by["inner"]["parent"] == "outer"
        assert by["inner"]["depth"] == 1
        assert by["outer"]["depth"] == 0 and "parent" not in by["outer"]
        assert by["inner"]["dur_s"] >= 0

    def test_exception_still_emits_and_pops(self, bus):
        with pytest.raises(RuntimeError):
            with obs.span("doomed", annotate=False):
                raise RuntimeError("boom")
        recs = [json.loads(x) for x in open(bus.path)]
        assert [r["name"] for r in recs] == ["doomed"]
        # The stack unwound: a following span is top-level again.
        with obs.span("after", annotate=False):
            pass
        recs = [json.loads(x) for x in open(bus.path)]
        assert recs[-1]["depth"] == 0

    def test_emit_span_feeds_registry_histogram(self, bus, registry):
        obs.emit_span("ckpt", 0.25, hist="train_ckpt_s", step=4)
        assert registry.histogram_summary("train_ckpt_s")["count"] == 1


# ---------------------------------------------------------------------
# registry.py
# ---------------------------------------------------------------------
class TestRegistry:
    def test_counters_gauges(self, registry):
        registry.inc("steps", 2)
        registry.inc("steps")
        registry.set_gauge("loss", 0.5)
        assert registry.counter("steps") == 3
        assert registry.gauge("loss") == 0.5

    def test_counter_rejects_negative(self, registry):
        with pytest.raises(ValueError, match="gauge"):
            registry.inc("steps", -1)

    def test_histogram_is_windowed(self, registry):
        for v in range(10):
            registry.observe("lat", float(v))
        s = registry.histogram_summary("lat")
        assert s["count"] == 4  # hist_window
        assert s["min"] == 6.0 and s["max"] == 9.0

    def test_prometheus_text(self, registry):
        registry.inc("steps")
        registry.set_gauge("serve/mfu", 0.4)  # needs sanitizing
        registry.observe("ttft", 1.0)
        text = registry.prometheus_text()
        assert "# TYPE tpu_hpc_steps counter" in text
        assert "tpu_hpc_serve_mfu 0.4" in text
        assert 'tpu_hpc_ttft{quantile="0.95"} 1.0' in text
        assert "tpu_hpc_ttft_count 1" in text

    def test_exposition_format_contract(self, registry):
        """The exposition-format contract: HELP precedes TYPE for
        described metrics (escaped per the text format), histogram
        summaries always carry _sum AND _count next to the
        quantiles, and undescribed metrics emit TYPE only."""
        registry.inc("reqs", 2, help="Requests served")
        registry.set_gauge("depth", 3.0,
                           help="Queue depth\nwith \\ tricky text")
        registry.observe("lat_ms", 2.0, help="Latency (ms)")
        registry.observe("lat_ms", 4.0)
        registry.inc("plain")  # no description -> no HELP line
        lines = registry.prometheus_text().splitlines()
        idx = {ln: i for i, ln in enumerate(lines)}
        assert "# HELP tpu_hpc_reqs Requests served" in idx
        assert idx["# HELP tpu_hpc_reqs Requests served"] + 1 == (
            idx["# TYPE tpu_hpc_reqs counter"]
        )
        # Escaping: newline -> \n, backslash -> \\ (one line each).
        assert (
            "# HELP tpu_hpc_depth Queue depth\\nwith \\\\ tricky text"
            in idx
        )
        assert "# TYPE tpu_hpc_lat_ms summary" in idx
        assert "tpu_hpc_lat_ms_sum 6.0" in idx
        assert "tpu_hpc_lat_ms_count 2" in idx
        assert 'tpu_hpc_lat_ms{quantile="0.5"} 3.0' in idx
        assert 'tpu_hpc_lat_ms{quantile="0.99"}' in " ".join(lines)
        assert not any(ln.startswith("# HELP tpu_hpc_plain")
                       for ln in lines)
        assert "# TYPE tpu_hpc_plain counter" in idx
        # First description wins; re-describing is a no-op.
        registry.describe("reqs", "changed")
        assert "# HELP tpu_hpc_reqs Requests served" in (
            registry.prometheus_text()
        )

    def test_write_prometheus_atomic_and_env_gated(
        self, registry, tmp_path, monkeypatch
    ):
        monkeypatch.delenv("TPU_HPC_PROM_FILE", raising=False)
        assert registry.write_prometheus() is None  # no env: no-op
        path = str(tmp_path / "metrics.prom")
        registry.inc("x")
        assert registry.write_prometheus(path) == path
        assert "tpu_hpc_x 1.0" in open(path).read()
        assert os.listdir(tmp_path) == ["metrics.prom"]  # no tmp left

    def test_emit_snapshot_validates(self, bus, registry):
        registry.inc("steps")
        rec = registry.emit_snapshot(step=7)
        validate_record(rec)
        assert rec["metrics"]["counters"]["steps"] == 1.0


# ---------------------------------------------------------------------
# quantiles.py: the estimator the regress gate trusts
# ---------------------------------------------------------------------
class TestQuantileMath:
    """The windowed-histogram quantiles feed the regression gate; they
    are pinned EXACTLY (not approximately) to numpy's default
    percentile estimator on known distributions."""

    @pytest.mark.parametrize("dist", ["uniform", "lognormal", "normal"])
    @pytest.mark.parametrize("q", [0.0, 0.5, 0.95, 0.99, 1.0])
    def test_matches_numpy_percentile(self, dist, q):
        import numpy as np

        from tpu_hpc.obs.quantiles import quantile

        rng = np.random.default_rng(42)
        vals = {
            "uniform": rng.uniform(0, 100, size=1001),
            "lognormal": rng.lognormal(2.0, 1.0, size=997),
            "normal": rng.normal(50, 10, size=256),
        }[dist]
        got = quantile(sorted(vals.tolist()), q)
        want = float(np.percentile(vals, 100 * q))
        assert got == pytest.approx(want, rel=1e-12), (dist, q)

    def test_edge_cases(self):
        from tpu_hpc.obs.quantiles import quantile

        assert quantile([], 0.5) == 0.0
        assert quantile([3.0], 0.0) == 3.0
        assert quantile([3.0], 0.99) == 3.0
        assert quantile([1.0, 2.0], 0.5) == 1.5
        with pytest.raises(ValueError, match="must be in"):
            quantile([1.0], 1.5)

    def test_summarize_keys(self):
        from tpu_hpc.obs.quantiles import summarize

        s = summarize([5.0, 1.0, 3.0])
        assert set(s) == {"p50", "p95", "p99"}
        assert s["p50"] == 3.0

    def test_registry_histogram_matches_numpy_on_window(
        self, registry,
    ):
        """The registry's summary quantiles are over the most recent
        window only -- and on that window they ARE numpy's
        percentiles."""
        import numpy as np

        rng = np.random.default_rng(7)
        vals = rng.lognormal(1.0, 0.8, size=10).tolist()
        for v in vals:
            registry.observe("lat", v)
        window = vals[-4:]  # registry fixture: hist_window=4
        s = registry.histogram_summary("lat")
        assert s["count"] == 4
        for key, q in (("p50", 50), ("p95", 95), ("p99", 99)):
            assert s[key] == pytest.approx(
                float(np.percentile(window, q)), rel=1e-12
            ), key

    def test_serve_meter_quantiles_match_numpy(self):
        """ServeMeter's TTFT quantiles come from the same estimator
        (the gate compares meter numbers against meter numbers)."""
        import numpy as np

        from tpu_hpc.serve.metrics import ServeMeter

        t = [0.0]
        meter = ServeMeter(clock=lambda: t[0])
        rng = np.random.default_rng(3)
        ttfts = rng.uniform(0.01, 0.2, size=25)
        for i, ttft in enumerate(ttfts):
            rid = f"r{i}"
            t[0] = float(i)
            meter.submitted(rid)
            meter.admitted(rid)
            t[0] = float(i) + float(ttft)
            meter.token(rid, first=True)
            meter.finished(rid)
        s = meter.summary()
        assert s["ttft_ms_p95"] == pytest.approx(
            1e3 * float(np.percentile(ttfts, 95)), rel=1e-9
        )
        assert s["ttft_ms_p99"] == pytest.approx(
            1e3 * float(np.percentile(ttfts, 99)), rel=1e-9
        )


# ---------------------------------------------------------------------
# stall.py
# ---------------------------------------------------------------------
class TestStallDetector:
    def test_quiet_until_warm_then_flags_breach(self, bus):
        det = obs.StallDetector(window=8, factor=3.0, min_samples=5)
        for step in range(5):
            assert det.observe(step, 1.0) is None
        info = det.observe(5, 10.0)
        assert info is not None and info["ratio"] == pytest.approx(10.0)
        recs = [json.loads(x) for x in open(bus.path)]
        assert [r["event"] for r in recs] == ["stall"]
        validate_file(bus.path)

    def test_stays_slow_rebaselines(self, bus):
        det = obs.StallDetector(window=4, factor=3.0, min_samples=2)
        for step in range(4):
            det.observe(step, 1.0)
        assert det.observe(4, 10.0) is not None
        # The slow regime persists; once the window is full of it,
        # the watermark has followed and alarming stops.
        flagged = [
            det.observe(5 + i, 10.0) is not None for i in range(6)
        ]
        assert flagged[-1] is False

    def test_heartbeat_extra_only_when_known(self):
        det = obs.StallDetector(min_samples=2)
        assert det.heartbeat_extra() == {}
        det.observe(1, 0.5)
        det.observe(2, 0.5)
        extra = det.heartbeat_extra()
        assert extra["step_s"] == 0.5
        assert extra["watermark_s"] == 0.5

    def test_rejects_bad_params(self):
        with pytest.raises(ValueError):
            obs.StallDetector(factor=1.0)
        with pytest.raises(ValueError):
            obs.StallDetector(min_samples=1)
        with pytest.raises(ValueError, match="min_samples"):
            # A window smaller than min_samples can never warm up:
            # the detector would be silently off forever.
            obs.StallDetector(window=3, min_samples=5)

    def test_zero_watermark_window_never_divides(self):
        """A window full of zero-duration steps (virtual-clock ticks
        that did no metered work -- chunked prefill filling every
        slot) must read as not-warm, not as an infinite-ratio stall:
        caught live as a ZeroDivisionError in the shared_prefix paged
        loadgen run."""
        det = obs.StallDetector(window=8, factor=3.0, min_samples=2)
        for step in range(4):
            assert det.observe(step, 0.0) is None
        assert det.observe(4, 1.0) is None  # no division, no stall
        # Once real durations dominate the window, breaches fire
        # again.
        for step in range(5, 11):
            det.observe(step, 1.0)
        assert det.observe(11, 10.0) is not None


# ---------------------------------------------------------------------
# schema.py
# ---------------------------------------------------------------------
class TestSchema:
    def _ok(self, **extra):
        return stamp({"event": "fault", "kind": "kill", **extra})

    def test_valid_record_passes(self):
        validate_record(self._ok())

    def test_unknown_event_rejected(self):
        with pytest.raises(SchemaError, match="unknown event"):
            validate_record(stamp({"event": "nope"}))

    def test_missing_required_rejected(self):
        with pytest.raises(SchemaError, match="missing required"):
            validate_record(stamp({"event": "fault"}))

    def test_closed_kind_rejects_unknown_field(self):
        with pytest.raises(SchemaError, match="unknown fields"):
            validate_record(self._ok(surprise=1))

    def test_open_kind_accepts_extras(self):
        validate_record(stamp({
            "event": "bench", "metric": "m", "value": 1, "unit": "u",
            "workload": "llama", "flash_blocks": {"q": 512},
        }))

    def test_schema_version_enforced(self):
        rec = self._ok()
        rec["schema_version"] = 999
        with pytest.raises(SchemaError, match="schema_version"):
            validate_record(rec)

    def test_validate_file_names_bad_line(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(
            json.dumps(self._ok()) + "\n" + "{not json}\n"
        )
        with pytest.raises(SchemaError, match="bad.jsonl:2"):
            validate_file(str(p))

    def test_stamp_never_overwrites(self):
        rec = stamp({"event": "fault", "kind": "kill", "time": 42.0},
                    run_id="mine")
        assert rec["time"] == 42.0 and rec["run_id"] == "mine"


# ---------------------------------------------------------------------
# report.py
# ---------------------------------------------------------------------
def _training_records():
    """A synthetic but schema-valid two-attempt run."""
    recs = [
        {"event": "run_start", "start_step": 0, "total_steps": 4,
         "n_devices": 8, "n_processes": 1, "device_kind": "cpu",
         "jax_version": "0", "run_id": "r",
         "config": {"model_flops_per_item": 1e9}},
        {"event": "span", "name": "compute", "dur_s": 8.0, "step": 2},
        {"event": "span", "name": "data", "dur_s": 1.0, "step": 2},
        {"event": "span", "name": "ckpt", "dur_s": 1.0, "step": 2},
        {"event": "epoch", "epoch": 0, "step": 2, "loss": 1.0,
         "items_per_s": 100.0, "items_per_s_per_device": 12.5,
         "s_per_step": 4.0},
        {"event": "run_end", "step": 2, "preempted": True,
         "attempt": 0, "resumed_from_step": 0,
         "goodput": {"total_s": 10.0, "productive_s": 8.0,
                     "ckpt_s": 1.0, "restore_s": 0.0, "other_s": 1.0,
                     "goodput": 0.8}},
        {"event": "stall", "step": 2, "step_s": 9.0,
         "watermark_s": 3.0, "ratio": 3.0},
        {"event": "epoch", "epoch": 1, "step": 4, "loss": 0.5,
         "items_per_s": 100.0, "items_per_s_per_device": 12.5,
         "s_per_step": 4.0},
        {"event": "run_end", "step": 4, "preempted": False,
         "attempt": 1, "resumed_from_step": 2,
         "goodput": {"total_s": 10.0, "productive_s": 9.0,
                     "ckpt_s": 0.5, "restore_s": 0.5, "other_s": 0.0,
                     "goodput": 0.9}},
    ]
    return [stamp(r) for r in recs]


class TestReport:
    def test_phase_breakdown_and_goodput(self):
        rep = build_report(_training_records())
        assert rep["phases"]["compute"]["total_s"] == 8.0
        assert rep["phases"]["compute"]["share"] == pytest.approx(0.8)
        gp = rep["goodput"]
        assert len(gp["attempts"]) == 2
        assert gp["combined"]["goodput"] == pytest.approx(17 / 20)
        assert len(rep["timeline"]) == 2
        assert rep["stalls"] == 1

    def test_nested_spans_do_not_double_count(self):
        """A child span's time is inside its parent's: only top-level
        spans feed the share denominator (review finding)."""
        recs = [stamp(r) for r in (
            {"event": "span", "name": "step", "dur_s": 10.0},
            {"event": "span", "name": "data", "dur_s": 4.0,
             "parent": "step", "depth": 1},
        )]
        phases = build_report(recs)["phases"]
        assert phases["step"]["share"] == pytest.approx(1.0)
        assert phases["data"]["share"] == pytest.approx(0.4)

    def test_mfu_weights_attempts_in_file_order(self):
        """A resumed run's MFU weights each attempt's chunks from its own
        start_step (review finding: seeding from the LAST run_start
        clamped earlier attempts to ~1-step weights)."""
        def epoch(step, rate, s_per_step):
            return {"event": "epoch", "epoch": 0, "step": step,
                    "loss": 1.0, "items_per_s": rate,
                    "items_per_s_per_device": rate,
                    "s_per_step": s_per_step}

        def start(step):
            return {"event": "run_start", "start_step": step,
                    "total_steps": 4, "n_devices": 1,
                    "n_processes": 1, "device_kind": "cpu",
                    "jax_version": "0",
                    "config": {"model_flops_per_item": 1.0}}

        recs = [stamp(r) for r in (
            start(0), epoch(2, 100.0, 1.0),   # attempt 0: 2s at 100/s
            start(2), epoch(4, 50.0, 1.0),    # attempt 1: 2s at 50/s
        )]
        rep = build_report(recs, peak_flops_per_device=1.0)
        # Equal 2-step chunks: plain average, NOT last-attempt-biased.
        assert rep["mfu"]["items_per_s"] == pytest.approx(75.0)

    def test_mfu_from_config_and_peak(self):
        rep = build_report(
            _training_records(), peak_flops_per_device=1e12,
        )
        # 100 items/s * 1e9 FLOP/item / (8 dev * 1e12 FLOP/s/dev)
        assert rep["mfu"]["mfu"] == pytest.approx(0.0125)

    def test_format_names_fused_phases(self):
        txt = format_report(build_report(_training_records()))
        assert "goodput" in txt and "Restart timeline" in txt
        # 'sync' was not measured on this run: the table says why
        # instead of silently omitting the canonical phase.
        assert "sync" in txt and "fused" in txt

    def test_cli_json_and_markdown(self, tmp_path, capsys):
        p = tmp_path / "run.jsonl"
        p.write_text(
            "\n".join(json.dumps(r) for r in _training_records())
        )
        assert report_main([str(p), "--json",
                            "--peak-flops", "1e12"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["goodput"]["combined"]["productive_s"] == 17.0
        assert report_main([str(p)]) == 0
        assert "Step-time breakdown" in capsys.readouterr().out

    def test_json_contract_pinned(self, tmp_path, capsys):
        """The driver contract obs/regress.py and CI consume: the
        JSON report carries schema_version; exit codes are 0 (report
        produced) / 2 (empty or invalid input) -- nothing else."""
        p = tmp_path / "run.jsonl"
        p.write_text(
            "\n".join(json.dumps(r) for r in _training_records())
        )
        assert report_main([str(p), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["schema_version"] == SCHEMA_VERSION
        # build_report (the --json payload) and the records agree on
        # the schema generation -- one constant, two consumers.
        from tpu_hpc.obs.report import build_report

        assert build_report(_training_records())["schema_version"] \
            == SCHEMA_VERSION

    def test_cli_rejects_invalid_and_missing(self, tmp_path, capsys):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"event": "mystery"}\n')
        assert report_main([str(bad)]) == 2
        assert report_main([str(tmp_path / "gone.jsonl")]) == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert report_main([str(empty)]) == 2
        capsys.readouterr()

    def test_cli_no_validate_salvages(self, tmp_path, capsys):
        p = tmp_path / "drifted.jsonl"
        recs = _training_records() + [{"event": "mystery"}]
        p.write_text("\n".join(json.dumps(r) for r in recs))
        assert report_main([str(p), "--no-validate"]) == 0
        capsys.readouterr()


# ---------------------------------------------------------------------
# integration: training -> one validated JSONL -> report  (the
# acceptance run for the PR: train and serve records share a schema)
# ---------------------------------------------------------------------
class TestTrainingReportSmoke:
    @pytest.fixture()
    def run_jsonl(self, mesh8, tmp_path):
        import jax
        import jax.numpy as jnp

        from tpu_hpc.config import TrainingConfig
        from tpu_hpc.parallel import dp
        from tpu_hpc.train import Trainer

        class DS:
            def batch_at(self, step, bs):
                k = jax.random.key(int(step) % 97)
                x = jax.random.normal(k, (bs, 4), jnp.float32)
                return x, x @ jnp.arange(4.0)

        def forward(params, model_state, batch, step_rng):
            x, y = batch
            pred = x @ params["w"]
            return jnp.mean((pred - y) ** 2), model_state, {}

        mpath = str(tmp_path / "run.jsonl")
        cfg = TrainingConfig(
            epochs=2, global_batch_size=16, steps_per_epoch=2,
            metrics_path=mpath, model_flops_per_item=1e6,
        )
        tr = Trainer(
            cfg, mesh8, forward, {"w": jnp.zeros((4,), jnp.float32)},
            param_pspecs=dp.param_pspecs(
                {"w": jnp.zeros((4,), jnp.float32)}
            ),
            batch_pspec=dp.batch_pspec(),
        )
        tr.fit(DS())
        return mpath

    def test_run_jsonl_validates_and_reports(self, run_jsonl, capsys):
        # Every record the Trainer wrote speaks the one schema.
        assert validate_file(run_jsonl) > 0
        events = [json.loads(x)["event"] for x in open(run_jsonl)]
        assert events[0] == "run_start" and events[-1] == "metrics"
        assert "span" in events and "run_end" in events
        # The report CLI turns it into a non-empty goodput/MFU/
        # step-time breakdown (sim CPU: peak supplied by flag).
        assert report_main([run_jsonl, "--json",
                            "--peak-flops", "1e12"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["phases"]["compute"]["total_s"] > 0
        assert rep["phases"]["compute"]["count"] == 2
        gp = rep["goodput"]["combined"]
        assert gp["productive_s"] > 0 and 0 < gp["goodput"] <= 1
        assert rep["mfu"] is not None and rep["mfu"]["mfu"] > 0
        assert rep["timeline"][0]["disposition"] == "completed"

    def test_report_module_cli(self, run_jsonl):
        """The exact command the docs teach: ``python -m
        tpu_hpc.obs.report run.jsonl`` (fresh interpreter -- the
        report must not need a jax backend)."""
        env = dict(os.environ)
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_hpc.obs.report", run_jsonl,
             "--peak-flops", "1e12"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "Step-time breakdown" in proc.stdout
        assert "goodput" in proc.stdout


# ---------------------------------------------------------------------
# integration: a faulted sim-mesh run leaves a flight-recorder dump
# ---------------------------------------------------------------------
FAULT_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_WORKER_HOSTNAMES"):
        os.environ.pop(var, None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpu_hpc.config import TrainingConfig
    from tpu_hpc.parallel import dp
    from tpu_hpc.runtime import MeshSpec, build_mesh
    from tpu_hpc.train import Trainer

    class DS:
        def batch_at(self, step, bs):
            k = jax.random.key(int(step) % 97)
            x = jax.random.normal(k, (bs, 4), jnp.float32)
            return x, x @ jnp.arange(4.0)

    def forward(params, model_state, batch, step_rng):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2), model_state, {}

    cfg = TrainingConfig(
        epochs=3, steps_per_epoch=2, global_batch_size=16,
        metrics_path=os.environ["WORK_METRICS"],
        checkpoint_dir=os.environ["WORK_CKPT"],
    )
    mesh = build_mesh(MeshSpec(axes={"data": 8}))
    params = {"w": jnp.zeros((4,), jnp.float32)}
    trainer = Trainer(
        cfg, mesh, forward, params,
        param_pspecs=dp.param_pspecs(params),
        batch_pspec=dp.batch_pspec(),
    )
    trainer.fit(DS())
    print("SURVIVED", flush=True)  # kill_at_step must prevent this
""")


class TestFaultedRunFlightDump:
    def test_sigkill_fault_leaves_pre_fault_evidence(self, tmp_path):
        """Acceptance: on the 8-device sim mesh, a TPU_HPC_FAULTS
        hard-kill run dumps a flight file holding the events leading
        up to the kill -- the fault record itself last."""
        worker = tmp_path / "worker.py"
        worker.write_text(FAULT_WORKER)
        env = dict(os.environ)
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
        env["WORK_METRICS"] = str(tmp_path / "run.jsonl")
        env["WORK_CKPT"] = str(tmp_path / "ckpt")
        env["TPU_HPC_FAULTS"] = "kill_at_step=4"
        env["TPU_HPC_FLIGHT_DIR"] = str(tmp_path / "flight")
        proc = subprocess.run(
            [sys.executable, str(worker)], capture_output=True,
            text=True, timeout=240, env=env, cwd=REPO,
        )
        assert proc.returncode == -9, proc.stderr[-2000:]
        assert "SURVIVED" not in proc.stdout
        dumps = os.listdir(tmp_path / "flight")
        assert len(dumps) == 1 and "fault_kill" in dumps[0]
        dump = os.path.join(str(tmp_path / "flight"), dumps[0])
        recs = [json.loads(x) for x in open(dump)]
        assert validate_file(dump) == len(recs)
        assert recs[0]["event"] == "flight_dump"
        assert recs[0]["reason"] == "fault_kill"
        events = [r["event"] for r in recs[1:]]
        # The ring replays the run up to the kill: the run_start, the
        # pre-fault progress, and the injected fault itself, in order.
        assert events[0] == "run_start"
        assert "span" in events and "epoch" in events
        assert events[-1] == "fault"
        assert recs[-1]["kind"] == "kill" and recs[-1]["step"] == 4
        # One run_id threads every record (the join key for forensics).
        assert len({r["run_id"] for r in recs}) == 1
