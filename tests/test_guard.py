"""The numeric-health guard + checkpoint integrity, end to end.

Chaos-matrix discipline (the ``chaos`` marker): every fault class the
resilience story claims to survive has a deterministic injection and a
test that drives the REAL Trainer / Orbax / supervisor through it.
Tier-1 keeps one fast representative per NEW fault class here
(nan-skip, nan-rollback-supervised, bitflip, spike, straggler,
quarantine); the full sweep over the matrix rides the ``slow`` marker
with the rest of the round gate.

The two acceptance proofs (ISSUE 9):

* ``TestSupervisedRollback``: ``nan_loss_at_step=N`` with the fault
  armed on EVERY attempt -- the guard detects the poisoned step
  exactly, quarantines, records a skip window, exits EXIT_ROLLBACK;
  the supervisor relaunches from the last-good checkpoint and the run
  can ONLY complete because the stream really skipped the poisoned
  data index. guard_rollback event + combined-goodput report pinned.
* ``TestBitflipChecksum``: ``bitflip_ckpt_at_step=N`` rewrites one
  tensor through orbax (parseable files, wrong content); only the
  sidecar checksums can catch it -- restore falls back to the older
  step, quarantines the corpse, and the events say so.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hpc import obs
from tpu_hpc.ckpt import CheckpointManager, integrity
from tpu_hpc.config import TrainingConfig
from tpu_hpc.obs.report import build_report
from tpu_hpc.obs.schema import load_records, validate_file
from tpu_hpc.resilience import (
    EXIT_ROLLBACK,
    GuardError,
    GuardPolicy,
    fault_plan_from_env,
)
from tpu_hpc.resilience import guard as guard_lib
from tpu_hpc.resilience.supervisor import run_supervised
from tpu_hpc.runtime import MeshSpec, build_mesh
from tpu_hpc.train import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------
# GuardPolicy classification (host-side, pure)
# ---------------------------------------------------------------------
def _row(loss_finite=1.0, grad_norm=1.0, update_norm=0.1, nonfinite=0):
    return {
        "health_loss_finite": loss_finite,
        "health_grad_norm": grad_norm,
        "health_update_norm": update_norm,
        "health_nonfinite": nonfinite,
    }


class TestGuardPolicy:
    def test_healthy_steps_feed_median(self):
        p = GuardPolicy(mode="skip", spike_factor=3.0)
        for s in range(4):
            assert p.classify(s, _row(grad_norm=1.0 + 0.01 * s)).healthy
        assert p.watermark == pytest.approx(1.015)

    def test_poisoned_on_nonfinite(self):
        p = GuardPolicy(mode="skip")
        assert p.classify(0, _row(loss_finite=0.0)).verdict == "poisoned"
        assert p.classify(1, _row(nonfinite=2)).verdict == "poisoned"
        assert (
            p.classify(2, _row(grad_norm=float("nan"))).verdict
            == "poisoned"
        )
        # Anomalous steps never enter the median window.
        assert p.watermark is None

    def test_spike_needs_warm_median(self):
        p = GuardPolicy(mode="skip", spike_factor=3.0, min_samples=3)
        # Cold: a huge first norm is NOT a spike (nothing to compare).
        assert p.classify(0, _row(grad_norm=100.0)).healthy
        for s in range(1, 4):
            p.classify(s, _row(grad_norm=1.0))
        v = p.classify(4, _row(grad_norm=50.0))
        assert v.verdict == "spike"
        assert v.ratio > 3.0
        # The spike did not poison the median it was judged against.
        before = p.watermark
        p.classify(5, _row(grad_norm=1.0))
        assert p.watermark == pytest.approx(before, rel=0.5)

    def test_wants_rollback_matrix(self):
        skip = GuardPolicy(mode="skip")
        roll = GuardPolicy(mode="rollback", spike_action="rollback")
        event = GuardPolicy(mode="rollback", spike_action="event")
        poisoned = skip.classify(0, _row(loss_finite=0.0))
        assert not skip.wants_rollback(poisoned)
        assert roll.wants_rollback(poisoned)
        for s in range(1, 5):
            for p in (roll, event):
                p.classify(s, _row())
        spike = roll.classify(5, _row(grad_norm=1e3))
        assert roll.wants_rollback(spike)
        spike2 = event.classify(5, _row(grad_norm=1e3))
        assert not event.wants_rollback(spike2)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="guard mode"):
            GuardPolicy(mode="offf")
        with pytest.raises(ValueError, match="guard_spike_action"):
            GuardPolicy(mode="skip", spike_action="explode")
        with pytest.raises(ValueError, match="guard_window"):
            GuardPolicy(mode="skip", window=1)
        cfg = TrainingConfig(guard_mode="off")
        assert GuardPolicy.from_config(cfg) is None
        cfg = TrainingConfig(guard_mode="skip", guard_spike_factor=5.0)
        p = GuardPolicy.from_config(cfg)
        assert p.mode == "skip" and p.spike_factor == 5.0
        with pytest.raises(ValueError, match="guard mode"):
            GuardPolicy.from_config(TrainingConfig(guard_mode="banana"))


class TestSkipWindows:
    def test_offset_and_boundary(self):
        windows = [
            {"from_step": 3, "data_from": 3, "data_to": 5},
            {"from_step": 10, "data_from": 13, "data_to": 13},
        ]
        assert guard_lib.offset_at(windows, 0) == 0
        assert guard_lib.offset_at(windows, 2) == 0
        assert guard_lib.offset_at(windows, 3) == 3
        assert guard_lib.offset_at(windows, 9) == 3
        assert guard_lib.offset_at(windows, 10) == 4
        assert guard_lib.next_boundary(windows, 0) == 3
        assert guard_lib.next_boundary(windows, 3) == 10
        assert guard_lib.next_boundary(windows, 10) is None

    def test_state_roundtrip(self, tmp_path):
        d = str(tmp_path)
        assert guard_lib.load_state(d)["skip_windows"] == []
        guard_lib.record_rollback(
            d, {"from_step": 4, "data_from": 4, "data_to": 5}
        )
        st = guard_lib.record_rollback(
            d, {"from_step": 2, "data_from": 2, "data_to": 2}
        )
        assert st["rollbacks"] == 2
        # Windows stay sorted by from_step regardless of append order.
        loaded = guard_lib.load_state(d)
        assert [w["from_step"] for w in loaded["skip_windows"]] == [2, 4]
        # A torn/garbage guard file degrades to empty, never crashes.
        (tmp_path / guard_lib.GUARD_STATE_FILE).write_text("{oops")
        assert guard_lib.load_state(d)["skip_windows"] == []


# ---------------------------------------------------------------------
# fault spec parsing (satellite: loud value errors, last-wins dupes)
# ---------------------------------------------------------------------
class TestFaultParse:
    def test_new_kinds_parse(self):
        plan = fault_plan_from_env({
            "TPU_HPC_FAULTS":
                "nan_loss_at_step=3,grad_spike_at_step=5,"
                "grad_spike_scale=100.0,bitflip_ckpt_at_step=6,"
                "straggler_ms=250,straggler_at_step=4,on_attempt=-1",
        })
        assert plan.nan_loss_at_step == 3
        assert plan.grad_spike_at_step == 5
        assert plan.grad_spike_scale == 100.0
        assert plan.bitflip_ckpt_at_step == 6
        assert plan.straggler_ms == 250.0
        assert plan.straggler_at_step == 4
        assert plan.on_attempt == -1
        assert plan.active  # -1 = every attempt
        assert fault_plan_from_env({
            "TPU_HPC_FAULTS": "nan_loss_at_step=3,on_attempt=-1",
            "TPU_HPC_ATTEMPT": "7",
        }).active

    def test_bad_int_value_names_key_and_spec(self):
        with pytest.raises(ValueError, match="kill_at_step") as ei:
            fault_plan_from_env(
                {"TPU_HPC_FAULTS": "kill_at_step=soon"}
            )
        msg = str(ei.value)
        assert "soon" in msg and "kill_at_step=soon" in msg
        assert "integer" in msg

    def test_bad_float_value_names_key_and_spec(self):
        with pytest.raises(ValueError, match="straggler_ms"):
            fault_plan_from_env(
                {"TPU_HPC_FAULTS": "straggler_ms=fast"}
            )

    def test_duplicate_key_last_wins(self):
        plan = fault_plan_from_env(
            {"TPU_HPC_FAULTS": "kill_at_step=2,kill_at_step=5"}
        )
        assert plan.kill_at_step == 5


# ---------------------------------------------------------------------
# checkpoint content integrity (unit level)
# ---------------------------------------------------------------------
class TestIntegrityUnit:
    def test_checksum_roundtrip_and_flip(self):
        state = {
            "w": jnp.arange(16, dtype=jnp.float32),
            "b": jnp.ones((4,), jnp.bfloat16),
        }
        sums = integrity.leaf_checksums(state)
        assert set(sums) == {"w", "b"}
        assert integrity.verify_tree(state, sums) == []
        flipped = dict(state)
        arr = np.array(state["w"], copy=True)
        arr.view(np.uint8)[5] ^= 0x01  # one bit
        flipped["w"] = jnp.asarray(arr)
        assert integrity.verify_tree(flipped, sums) == ["w"]

    def test_dtype_switch_is_not_corruption(self):
        state = {"mu": jnp.ones((8,), jnp.float32)}
        sums = integrity.leaf_checksums(state)
        cast = {"mu": state["mu"].astype(jnp.bfloat16)}
        # orbax's legal restore-into-different-dtype: skipped, clean.
        assert integrity.verify_tree(cast, sums) == []

    def test_unknown_paths_skipped(self):
        sums = integrity.leaf_checksums({"a": jnp.ones((2,))})
        assert integrity.verify_tree({"b": jnp.zeros((2,))}, sums) == []

    def test_async_manager_writes_and_verifies_checksums(
        self, tmp_path, fresh_bus
    ):
        """Async managers compute the sidecar checksums on a
        background thread (the save-side device_get+crc must not
        serialize the hot loop); restore joins the thread and still
        verifies."""
        from tpu_hpc.reshard.elastic import read_sidecar

        ck = str(tmp_path / "ck")
        mgr = CheckpointManager(ck, async_save=True)
        state = {"w": jnp.arange(8, dtype=jnp.float32)}
        mgr.save(state, step=1)
        restored = mgr.restore_latest(
            {"w": jnp.zeros((8,), jnp.float32)}
        )
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(8, dtype=np.float32)
        )
        meta = read_sidecar(ck, 1)
        assert meta is not None and "checksums" in meta
        mgr.close()


# ---------------------------------------------------------------------
# in-process trainer chaos (the fast tier-1 representatives)
# ---------------------------------------------------------------------
class LinearDS:
    """Deterministic per-step batches keyed by the DATA index."""

    def batch_at(self, step, bs):
        k = jax.random.key(int(step) % 97)
        x = jax.random.normal(k, (bs, 4), jnp.float32)
        return x, x @ jnp.arange(4.0)


def _forward(params, model_state, batch, step_rng):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2), model_state, {}


@pytest.fixture()
def fresh_bus():
    """Isolated event bus per test: no sink, no flight dir, fresh
    run_id -- a previous test's flight_dir must not swallow dumps."""
    prev = obs.set_bus(obs.EventBus(path="", flight_dir=""))
    yield obs.get_bus()
    obs.set_bus(prev)


def _make_trainer(mesh, ckpt_dir, metrics, guard_mode="rollback",
                  epochs=3, **cfg_kw):
    cfg = TrainingConfig(
        epochs=epochs, steps_per_epoch=2, global_batch_size=8,
        learning_rate=1e-2, save_every=1, checkpoint_dir=ckpt_dir,
        metrics_path=metrics, guard_mode=guard_mode, **cfg_kw,
    )
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    return Trainer(
        cfg, mesh, _forward, {"w": jnp.zeros((4,), jnp.float32)},
        checkpoint_manager=mgr,
    )


@pytest.mark.chaos
class TestGuardSkip:
    def test_nan_batch_skipped_on_device(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """guard_mode='skip': a NaN loss at data index 3 drops that
        update on-device -- params stay finite, the stream advances,
        the run completes, and the verdict is a schema-stamped event."""
        monkeypatch.setenv(
            "TPU_HPC_FAULTS", "nan_loss_at_step=3,on_attempt=-1"
        )
        metrics = str(tmp_path / "run.jsonl")
        tr = _make_trainer(
            mesh8, str(tmp_path / "ck"), metrics, guard_mode="skip",
            capture_on_anomaly=True,
        )
        res = tr.fit(LinearDS())
        assert int(jax.device_get(tr.state.step)) == 6
        assert np.isfinite(res["final_loss"])
        assert np.isfinite(np.asarray(tr.state.params["w"])).all()
        assert res["rolled_back"] is False
        recs = load_records(metrics)
        verdicts = [
            r for r in recs if r["event"] == "guard_verdict"
        ]
        assert [(v["step"], v["verdict"], v["action"])
                for v in verdicts] == [(3, "poisoned", "skip")]
        assert verdicts[0]["data_index"] == 3
        # The symptom->evidence join (obs/trace.py): the verdict and
        # the guard-triggered capture share the poisoned STEP's trace
        # id, so the evidence bundle greps to the record that caused
        # it.
        assert verdicts[0]["trace_id"].endswith(":step:3")
        caps = [
            r for r in recs if r["event"] == "capture_triggered"
        ]
        assert len(caps) == 1
        assert caps[0]["reason"] == "guard_poisoned"
        assert caps[0]["trace_id"] == verdicts[0]["trace_id"]
        assert validate_file(metrics) > 0

    def test_skip_without_anomaly_is_bit_identical_and_same_compiles(
        self, mesh8, tmp_path, fresh_bus
    ):
        """The zero-cost claim, pinned: on a healthy run the guard
        changes NOTHING -- final params bit-identical to guard-off,
        and the same number of compiled epoch programs (the health
        vector rides the existing jitted chunk; no extra compiles in
        steady state)."""
        ds = LinearDS()
        tr_off = _make_trainer(
            mesh8, str(tmp_path / "a"), "", guard_mode="off"
        )
        tr_on = _make_trainer(
            mesh8, str(tmp_path / "b"), "", guard_mode="skip"
        )
        tr_off.fit(ds)
        tr_on.fit(ds)
        np.testing.assert_array_equal(
            np.asarray(tr_off.state.params["w"]),
            np.asarray(tr_on.state.params["w"]),
        )
        # One AOT-compiled executable per distinct chunk length,
        # guard on or off: enabling the guard must not change the
        # steady-state compile count.
        assert len(tr_on._epoch_fns) == len(tr_off._epoch_fns)


@pytest.mark.chaos
class TestGuardSpike:
    def test_spike_detected_against_rolling_median(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """grad_spike_at_step: a finite 1e4x gradient at data index 5
        is flagged 'spike' against the rolling healthy median; the
        default action is an event (record, keep going)."""
        monkeypatch.setenv(
            "TPU_HPC_FAULTS", "grad_spike_at_step=5,on_attempt=-1"
        )
        metrics = str(tmp_path / "run.jsonl")
        tr = _make_trainer(
            mesh8, str(tmp_path / "ck"), metrics,
            guard_mode="skip", epochs=4, guard_spike_factor=10.0,
        )
        res = tr.fit(LinearDS())
        assert int(jax.device_get(tr.state.step)) == 8
        assert res["rolled_back"] is False
        verdicts = [
            r for r in load_records(metrics)
            if r["event"] == "guard_verdict"
        ]
        spikes = [v for v in verdicts if v["verdict"] == "spike"]
        # Detection onset is exact; the injected update knocks the
        # model off its trajectory, so the immediately following
        # (genuine) recovery steps may legitimately spike too.
        assert spikes and spikes[0]["step"] == 5
        assert all(v["step"] >= 5 for v in spikes)
        assert spikes[0]["action"] == "event"
        assert spikes[0]["ratio"] > 10.0


@pytest.mark.chaos
class TestGuardRollbackInProcess:
    def test_rollback_pair_skips_poisoned_window_deterministically(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """The rollback round trip without the supervisor: attempt 0
        poisons at data index 3, rolls back (quarantine + skip window
        + rolled_back=True => EXIT_ROLLBACK); the relaunch -- with the
        fault STILL armed -- completes because the stream skipped the
        index. Run twice: bit-identical final params (deterministic
        under seed)."""
        monkeypatch.setenv(
            "TPU_HPC_FAULTS", "nan_loss_at_step=3,on_attempt=-1"
        )

        def pair(tag):
            ck = str(tmp_path / tag / "ck")
            metrics = str(tmp_path / tag / "run.jsonl")
            tr0 = _make_trainer(mesh8, ck, metrics)
            r0 = tr0.fit(LinearDS())
            assert r0["rolled_back"] is True
            from tpu_hpc.resilience import exit_code_for

            assert exit_code_for(
                r0["preempted"], r0["rolled_back"]
            ) == EXIT_ROLLBACK
            state = guard_lib.load_state(ck)
            assert state["skip_windows"] == [
                {"from_step": 3, "data_from": 3, "data_to": 3}
            ]
            tr1 = _make_trainer(mesh8, ck, metrics)
            r1 = tr1.fit(LinearDS())
            assert r1["rolled_back"] is False
            assert int(jax.device_get(tr1.state.step)) == 6
            assert np.isfinite(r1["final_loss"])
            return np.asarray(tr1.state.params["w"]), metrics

        w_a, metrics = pair("a")
        w_b, _ = pair("b")
        np.testing.assert_array_equal(w_a, w_b)

        recs = load_records(metrics)
        rollbacks = [
            r for r in recs if r["event"] == "guard_rollback"
        ]
        # Detection names the poisoned step exactly (within 1 step).
        assert len(rollbacks) == 1
        assert rollbacks[0]["first_bad"] == 3
        assert rollbacks[0]["to_step"] == 2
        # The resumed attempt's run_start proves the restore target.
        starts = [r for r in recs if r["event"] == "run_start"]
        assert starts[-1]["start_step"] == 2

    def test_rollback_without_predating_checkpoint_is_loud(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """Anomaly before the first save: the guard must fail loudly,
        not silently restart-from-0 into the same poison."""
        monkeypatch.setenv(
            "TPU_HPC_FAULTS", "nan_loss_at_step=0,on_attempt=-1"
        )
        tr = _make_trainer(
            mesh8, str(tmp_path / "ck"), "", guard_mode="rollback"
        )
        with pytest.raises(GuardError, match="no checkpoint predates"):
            tr.fit(LinearDS())

    def test_rollback_mode_requires_checkpoint_manager(self, mesh8):
        cfg = TrainingConfig(guard_mode="rollback")
        with pytest.raises(ValueError, match="checkpoint_manager"):
            Trainer(
                cfg, mesh8, _forward,
                {"w": jnp.zeros((4,), jnp.float32)},
            )


@pytest.mark.chaos
class TestBitflipChecksum:
    def test_silent_corruption_caught_and_quarantined(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """bitflip_ckpt_at_step=6: the final snapshot is rewritten
        through orbax with one flipped bit -- parseable, wrong. The
        next restore verifies checksums, treats the mismatch like a
        torn write (falls back to step 4), quarantines the corpse as
        ``6.corrupt`` so later restarts never re-probe it, and emits
        ckpt_integrity + ckpt_fallback events the report can see."""
        ck = str(tmp_path / "ck")
        metrics = str(tmp_path / "run.jsonl")
        monkeypatch.setenv("TPU_HPC_FAULTS", "bitflip_ckpt_at_step=6")
        tr = _make_trainer(mesh8, ck, metrics, guard_mode="off")
        tr.fit(LinearDS())
        assert tr.checkpoint_manager.all_steps() == [2, 4, 6]

        monkeypatch.setenv("TPU_HPC_ATTEMPT", "1")  # fault scoped out
        tr2 = _make_trainer(
            mesh8, ck, metrics, guard_mode="off", epochs=4
        )
        assert tr2.maybe_resume() == 4  # fell back below 6
        assert os.path.isdir(os.path.join(ck, "6.corrupt"))
        # The quarantined step's sidecar went with it (the replayed
        # save below will write a FRESH step 6 + sidecar).
        assert not os.path.exists(
            os.path.join(ck, ".tpu_hpc_meta", "6.json")
        )
        res = tr2.fit(LinearDS())
        assert int(jax.device_get(tr2.state.step)) == 8
        assert np.isfinite(res["final_loss"])

        recs = load_records(metrics)
        integ = [r for r in recs if r["event"] == "ckpt_integrity"]
        # One mismatch for the flipped step, then verified-ok restores
        # of the fallback step (once for the explicit maybe_resume
        # above, once inside fit's own resume).
        assert [(r["step"], r["verdict"]) for r in integ] == [
            (6, "mismatch"), (4, "ok"), (4, "ok"),
        ]
        falls = [r for r in recs if r["event"] == "ckpt_fallback"]
        assert len(falls) == 1 and falls[0]["step"] == 6
        assert falls[0]["quarantined"] == "6.corrupt"
        starts = [r for r in recs if r["event"] == "run_start"]
        assert starts[-1]["start_step"] == 4  # fell back below 6
        # Report + regress gate surface all of it.
        rep = build_report(recs)
        assert rep["ckpt"]["fallbacks"] == 1
        assert rep["ckpt"]["integrity_failures"] == 1
        from tpu_hpc.obs.regress import report_metrics

        flat = report_metrics(rep)
        assert flat["ckpt.fallbacks"] == 1.0
        assert flat["ckpt.integrity_failures"] == 1.0

    def test_bitflip_is_deterministic(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """Same seed, same flip, same fallback target -- the chaos
        matrix must be replayable."""
        targets = []
        for tag in ("a", "b"):
            ck = str(tmp_path / tag)
            monkeypatch.setenv(
                "TPU_HPC_FAULTS", "bitflip_ckpt_at_step=4"
            )
            monkeypatch.delenv("TPU_HPC_ATTEMPT", raising=False)
            tr = _make_trainer(
                mesh8, ck, "", guard_mode="off", epochs=2
            )
            tr.fit(LinearDS())
            monkeypatch.setenv("TPU_HPC_ATTEMPT", "1")
            tr2 = _make_trainer(
                mesh8, ck, "", guard_mode="off", epochs=3
            )
            tr2.fit(LinearDS())
            targets.append(
                (
                    int(jax.device_get(tr2.state.step)),
                    sorted(
                        d for d in os.listdir(ck)
                        if d.endswith(".corrupt")
                    ),
                )
            )
        assert targets[0] == targets[1] == (6, ["4.corrupt"])


@pytest.mark.chaos
class TestQuarantineTornWrite:
    def test_torn_write_quarantined_no_reprobe(
        self, mesh8, tmp_path, monkeypatch, fresh_bus
    ):
        """The torn-write fault (garbage files) now also quarantines:
        the second restart must find the corpse already renamed aside
        instead of re-probing it through the retry ladder."""
        ck = str(tmp_path / "ck")
        monkeypatch.setenv("TPU_HPC_FAULTS", "corrupt_ckpt_at_step=6")
        tr = _make_trainer(mesh8, ck, "", guard_mode="off")
        tr.fit(LinearDS())

        monkeypatch.setenv("TPU_HPC_ATTEMPT", "1")
        tr2 = _make_trainer(mesh8, ck, "", guard_mode="off", epochs=3)
        assert tr2.maybe_resume() == 4
        assert os.path.isdir(os.path.join(ck, "6.corrupt"))
        assert 6 not in tr2.checkpoint_manager.all_steps()
        # A third manager never even sees step 6.
        mgr3 = CheckpointManager(ck, async_save=False)
        assert 6 not in mgr3.all_steps()
        mgr3.close()

    def test_systemic_failure_never_quarantines(
        self, tmp_path, fresh_bus
    ):
        """Quarantine is deferred until an OLDER step restores
        successfully: a systemic failure (wrong relaunch config --
        every step fails structurally) must leave every snapshot and
        sidecar in place, keep the typed loud-failure error, and let
        a corrected relaunch restore normally."""
        from tpu_hpc.ckpt import TopologyMismatchError

        ck = str(tmp_path / "ck")
        mgr = CheckpointManager(ck, async_save=False)
        state = {"w": jnp.ones((4,), jnp.float32)}
        mgr.save(state, step=2)
        mgr.save(state, step=4)
        with pytest.raises(TopologyMismatchError, match="shape"):
            mgr.restore_latest({"w": jnp.zeros((5,), jnp.float32)})
        # Nothing renamed, nothing deleted: the snapshots are FINE.
        assert mgr.all_steps() == [2, 4]
        assert not any(
            d.endswith(".corrupt") for d in os.listdir(ck)
        )
        restored = mgr.restore_latest(
            {"w": jnp.zeros((4,), jnp.float32)}
        )
        assert restored is not None
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.ones((4,), np.float32)
        )
        mgr.close()


@pytest.mark.chaos
class TestStraggler:
    def test_straggler_delay_trips_stall_watermark(
        self, tmp_path, monkeypatch, fresh_bus
    ):
        """straggler_ms from straggler_at_step: the injected per-chunk
        delay lands INSIDE the metered window, so the rolling
        step-time watermark flags the degradation (a ``stall`` event)
        -- the gray-failure class binary liveness cannot see."""
        monkeypatch.setenv(
            "TPU_HPC_FAULTS",
            "straggler_ms=400,straggler_at_step=7,on_attempt=-1",
        )
        metrics = str(tmp_path / "run.jsonl")
        mesh1 = build_mesh(
            MeshSpec(axes={"data": 1}), devices=jax.devices()[:1]
        )
        cfg = TrainingConfig(
            epochs=8, steps_per_epoch=1, global_batch_size=8,
            learning_rate=1e-2, metrics_path=metrics,
        )
        tr = Trainer(
            cfg, mesh1, _forward,
            {"w": jnp.zeros((4,), jnp.float32)},
        )
        tr.fit(LinearDS())
        recs = load_records(metrics)
        stalls = [r for r in recs if r["event"] == "stall"]
        assert stalls, "injected 400ms delay never tripped the stall"
        assert all(r["step"] >= 7 for r in stalls)
        assert any(
            r["event"] == "fault" and r["kind"] == "straggler"
            for r in obs.get_bus().ring()
        )


# ---------------------------------------------------------------------
# THE acceptance run: supervised rollback, subprocess end to end
# ---------------------------------------------------------------------
WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("TPU_VISIBLE_DEVICES", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                "TPU_WORKER_HOSTNAMES"):
        os.environ.pop(var, None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpu_hpc import resilience
    from tpu_hpc.ckpt import CheckpointManager
    from tpu_hpc.config import TrainingConfig
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

    ckpt_dir = os.environ["WORK_CKPT"]
    cfg = TrainingConfig(
        epochs=int(os.environ.get("WORK_EPOCHS", "3")),
        steps_per_epoch=2, global_batch_size=8, learning_rate=1e-2,
        save_every=1, checkpoint_dir=ckpt_dir,
        metrics_path=os.environ.get("WORK_METRICS", ""),
        guard_mode=os.environ.get("WORK_GUARD", "off"),
        guard_spike_action=os.environ.get("WORK_SPIKE_ACTION", "event"),
    )
    mesh = build_mesh(
        MeshSpec(axes={"data": 1}), devices=jax.devices()[:1]
    )
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    trainer = Trainer(
        cfg, mesh, forward, {"w": jnp.zeros((4,), jnp.float32)},
        checkpoint_manager=mgr,
    )
    result = trainer.fit(DS())
    print("FINAL_STEP", int(jax.device_get(trainer.state.step)),
          flush=True)
    sys.exit(resilience.exit_code_for(
        result["preempted"], result.get("rolled_back", False)
    ))
""")


@pytest.fixture()
def worker(tmp_path):
    path = tmp_path / "worker.py"
    path.write_text(WORKER)

    def run(env_extra, timeout=240, argv_prefix=()):
        env = dict(os.environ)
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
        env["WORK_CKPT"] = str(tmp_path / "ckpts")
        env["WORK_METRICS"] = str(tmp_path / "run.jsonl")
        env.update({k: str(v) for k, v in env_extra.items()})
        return subprocess.run(
            [*argv_prefix, sys.executable, str(path)],
            capture_output=True, text=True, timeout=timeout,
            env=env, cwd=REPO,
        )

    return run


def _metrics(tmp_path):
    path = tmp_path / "run.jsonl"
    if not path.exists():
        return []
    return [json.loads(x) for x in open(path)]


@pytest.mark.chaos
class TestSupervisedRollback:
    def test_nan_rollback_relaunch_completes(self, worker, tmp_path):
        """ISSUE 9 acceptance: nan_loss_at_step=3 (armed on EVERY
        attempt) under the supervisor. The guard detects the poisoned
        step exactly, exits EXIT_ROLLBACK (a healthy-process exit:
        restart budget untouched, rollback budget charged), the
        relaunch resumes from the last-good checkpoint, skips the
        poisoned data index -- the ONLY way it can survive with the
        fault still armed -- and completes, leaving a guard_rollback
        event and a combined-goodput report."""
        sup_dir = str(tmp_path / "sup")
        proc = worker(
            {
                "TPU_HPC_FAULTS": "nan_loss_at_step=3,on_attempt=-1",
                "WORK_GUARD": "rollback",
            },
            argv_prefix=(
                sys.executable, "-m", "tpu_hpc.resilience.supervisor",
                "--max-restarts", "0", "--max-rollbacks", "2",
                "--log-dir", sup_dir, "--backoff", "0.1", "--",
            ),
        )
        assert proc.returncode == 0, proc.stderr[-3000:]

        events = [
            json.loads(x)
            for x in open(os.path.join(sup_dir, "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [EXIT_ROLLBACK, 0]
        assert "guard rollback" in ends[0]["meaning"]
        restarts = [e for e in events if e["event"] == "restarting"]
        assert restarts[0]["why"] == (
            "guard rollback to last-good snapshot"
        )

        a1 = open(os.path.join(sup_dir, "run.attempt1.log")).read()
        assert "FINAL_STEP 6" in a1

        recs = _metrics(tmp_path)
        rollbacks = [
            r for r in recs if r["event"] == "guard_rollback"
        ]
        assert len(rollbacks) == 1
        assert rollbacks[0]["first_bad"] == 3  # detected exactly
        assert rollbacks[0]["to_step"] == 2
        starts = [r for r in recs if r["event"] == "run_start"]
        assert starts[-1]["start_step"] == 2
        # Combined-goodput record: both attempts in one report, plus
        # the guard section naming the rollback.
        rep = build_report(recs)
        assert rep["goodput"] is not None
        assert rep["goodput"]["combined"]["productive_s"] > 0
        assert rep["guard"] is not None
        assert len(rep["guard"]["rollbacks"]) == 1
        assert rep["guard"]["lost_steps"] == 2  # steps 2..3 redone
        from tpu_hpc.obs.regress import report_metrics

        flat = report_metrics(rep)
        assert flat["guard.rollbacks"] == 1.0
        # The skip window survived on disk for any later restart.
        state = guard_lib.load_state(str(tmp_path / "ckpts"))
        assert state["rollbacks"] == 1


class TestRollbackBudget:
    def test_rollbacks_bounded_separately_from_failures(self, tmp_path):
        """EXIT_ROLLBACK exits never burn the restart budget but are
        bounded by --max-rollbacks: a run that keeps poisoning itself
        must not relaunch forever."""
        rc = run_supervised(
            [sys.executable, "-c",
             f"import sys; sys.exit({EXIT_ROLLBACK})"],
            max_restarts=5, max_rollbacks=2,
            log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == EXIT_ROLLBACK
        events = [
            json.loads(x)
            for x in open(os.path.join(str(tmp_path),
                                       "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == [EXIT_ROLLBACK] * 3
        give = [e for e in events if e["event"] == "giving_up"]
        assert "rollback budget" in give[0]["why"]

    def test_rollback_then_success_under_tight_restart_budget(
        self, tmp_path
    ):
        """max_restarts=0 with one rollback: still succeeds -- the
        rollback exit must not consume the (empty) failure budget."""
        child = (
            "import os, sys; "
            "sys.exit(0 if int(os.environ['TPU_HPC_ATTEMPT']) >= 1 "
            f"else {EXIT_ROLLBACK})"
        )
        rc = run_supervised(
            [sys.executable, "-c", child],
            max_restarts=0, max_rollbacks=3,
            log_dir=str(tmp_path), backoff=0.01,
        )
        assert rc == 0


class TestRegressGateDirections:
    def test_robustness_counters_are_lower_is_better(self):
        """Satellite: the regress gate must treat guard/rollback/
        fallback counts as regressions when they go UP -- a robustness
        gate, not just a perf gate."""
        from tpu_hpc.obs.regress import compare, lower_is_better

        for name in (
            "guard.rollbacks", "guard.poisoned", "guard.spikes",
            "guard.skipped", "guard.lost_steps", "ckpt.fallbacks",
            "ckpt.integrity_failures",
        ):
            assert lower_is_better(name), name
        violations, checked = compare(
            {"guard.rollbacks": 0.0, "ckpt.fallbacks": 0.0},
            {"guard.rollbacks": 2.0, "ckpt.fallbacks": 1.0},
        )
        assert checked == 2
        assert {v["metric"] for v in violations} == {
            "guard.rollbacks", "ckpt.fallbacks",
        }


# ---------------------------------------------------------------------
# the full chaos matrix (slow tier: every fault class, one sweep)
# ---------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.chaos
class TestChaosMatrixFull:
    @pytest.mark.parametrize(
        "name,faults,guard,spike_action,sup_args,expect_rcs",
        [
            (
                "nan-skip",
                "nan_loss_at_step=3,on_attempt=-1", "skip", "event",
                ("--max-restarts", "0"), [0],
            ),
            (
                "nan-rollback",
                "nan_loss_at_step=3,on_attempt=-1", "rollback",
                "event",
                ("--max-restarts", "0", "--max-rollbacks", "2"),
                [EXIT_ROLLBACK, 0],
            ),
            (
                "spike-rollback",
                "grad_spike_at_step=5,on_attempt=-1", "rollback",
                "rollback",
                ("--max-restarts", "0", "--max-rollbacks", "2"),
                [EXIT_ROLLBACK, 0],
            ),
            (
                "kill-guarded",
                "kill_at_step=4", "skip", "event",
                ("--max-restarts", "2"), [137, 0],
            ),
        ],
    )
    def test_matrix(
        self, worker, tmp_path, name, faults, guard, spike_action,
        sup_args, expect_rcs,
    ):
        """Every row: inject, supervise, survive, leave evidence."""
        sup_dir = str(tmp_path / "sup")
        epochs = "4" if "spike" in name else "3"
        proc = worker(
            {
                "TPU_HPC_FAULTS": faults,
                "WORK_GUARD": guard,
                "WORK_SPIKE_ACTION": spike_action,
                "WORK_EPOCHS": epochs,
            },
            argv_prefix=(
                sys.executable, "-m", "tpu_hpc.resilience.supervisor",
                *sup_args, "--log-dir", sup_dir, "--backoff", "0.1",
                "--",
            ),
        )
        assert proc.returncode == 0, (name, proc.stderr[-3000:])
        events = [
            json.loads(x)
            for x in open(os.path.join(sup_dir, "supervisor.jsonl"))
        ]
        ends = [e for e in events if e["event"] == "attempt_end"]
        assert [e["rc"] for e in ends] == expect_rcs, name
        final = int(epochs) * 2
        last_log = os.path.join(
            sup_dir, f"run.attempt{len(expect_rcs) - 1}.log"
        )
        assert f"FINAL_STEP {final}" in open(last_log).read(), name
        recs = _metrics(tmp_path)
        if EXIT_ROLLBACK in expect_rcs:
            assert any(
                r["event"] == "guard_rollback" for r in recs
            ), name
        elif guard == "skip" and "nan" in faults:
            assert any(
                r["event"] == "guard_verdict"
                and r["action"] == "skip"
                for r in recs
            ), name
